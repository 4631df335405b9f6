"""Engine: decode time per step, from the engine's own ``step_log``
(host clock around each fused decode chunk, to ``block_until_ready``):
seconds summed over the chunks that returned in the window, over the
decode steps they ran."""


def read(run):
    logs = [run.step_log[c.log] for c in run.rec.calls
            if c.kind == "decode_chunk" and run.in_window(c)]
    steps = sum(e["steps"] for e in logs)
    if not steps:
        return None
    return 1e3 * sum(e["seconds"] for e in logs) / steps
