"""Engine: the host's time between two decode chunks of one ``generate``
call, from the engine's own ``step_log``: the next chunk's dispatch stamp
minus this chunk's ready stamp (its ``decode_chunk`` phase), in ms, averaged
over the boundaries between consecutive chunks of one call.  Both chunks
return in the window and neither is traced, so the profiler's own cost is
left out.  The device idles for most of each such gap."""


def boundaries(run, traced: bool = False) -> list:
    """The gaps (ns) between consecutive chunks of one ``generate`` call
    that return in the window: neither chunk traced, or with ``traced``
    both."""
    spans = ({c.n for c, _ in run.traced_calls("decode_chunk")}
             if run.trace is not None else set())
    chunks = [c for c in run.rec.calls if c.kind == "decode_chunk"]
    gaps = []
    for a, b in zip(chunks, chunks[1:]):
        ea, eb = run.step_log[a.log], run.step_log[b.log]
        if ea.get("gen") is None or ea.get("gen") != eb.get("gen") or \
                not (run.in_window(a) and run.in_window(b)) or \
                {a.n in spans, b.n in spans} != {traced}:
            continue
        ready = dict((p, e) for p, _, e in ea.get("phases", ()))
        dispatch = dict((p, s) for p, s, _ in eb.get("phases", ()))
        if "decode_chunk" in ready and "decode_chunk" in dispatch:
            gaps.append(dispatch["decode_chunk"] - ready["decode_chunk"])
    return gaps


def read(run):
    gaps = boundaries(run)
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
