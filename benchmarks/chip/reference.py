"""The plain reference, its generic part: teacher forcing over the
program's served tokens, in blocks, and the gaps read at every served
position.  Each family writes only its forward (``families/<family>.py``
``logits``): the published decoder in float32 ``jax.numpy`` at the
highest matmul precision, with no cache, kernel or batching of the
program's, built from the helpers here.  It imports nothing of the
program.

Given prompts and the tokens the program served, ``compare`` runs each
whole sequence once and reads, at every served position, the gap by
which the served token's logit lies below the reference's best.  A
greedy program that computes what the configuration states serves the
reference's best token up to rounding, so its widest gap is small.

The control puts the next lower precision than the configuration's in
the program's place (the step a later PR would be tempted by): for a
bfloat16 model, the same forward with every weight matrix quantized to
int8 per output channel and the activations in bfloat16; for a float32
model, weights and activations in bfloat16 (``_mm``).  At the same
positions it reads the gap of the token the control ranks first.

Sequences run in blocks of ``block_rows`` rows padded to one length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _quant_int8(w, axis):
    """Symmetric int8 per output channel: scale by the largest |w| over
    the input axis ``axis``, round, and scale back."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    return (jnp.round(w / jnp.maximum(s, 1e-30)) * s)


def _mm(m, x, w, spec, control, in_axes=(0,)):
    """einsum of activations and a weight: float32 at HIGHEST for the
    reference; for the control, bfloat16 activations and weights one step
    below the configuration's dtype (``m.dtype``)."""
    if control:
        if m.dtype != "float32":
            w = _quant_int8(w, in_axes)
        w = w.astype(jnp.bfloat16)
        return jnp.einsum(spec, x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, x, w.astype(jnp.float32), precision=HIGHEST)


def _rms(x, g, eps):
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE as in Qwen2 and InternLM2: x [b, s, h, dh]."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, :, None].astype(jnp.float32) * inv          # [b, s, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(logits, m, w, tokens, served, alt):
    """Per position: reference best minus the reference's logit of the
    served token, and of the control's first choice ``alt``."""
    lg = logits(m, False, w, tokens)
    best = lg.max(-1)
    at = lambda t: jnp.take_along_axis(lg, t[..., None], -1)[..., 0]  # noqa
    return best - at(served), best - at(alt)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _control_choice(logits, m, w, tokens):
    return jnp.argmax(logits(m, True, w, tokens), -1).astype(jnp.int32)


def compare(logits, m, w, samples, length: int, block_rows: int,
            control: bool = False) -> dict:
    """``logits(m, control, w, tokens)``: the family's forward, ``[b, s]``
    token ids to ``[b, s, vocab]`` float32 logits, jitted with ``m`` and
    ``control`` static; ``m`` its shape and ``w`` its reference weights.
    ``samples``: (prompt ids, served ids) pairs.  Every sequence is
    padded to ``length``.  Returns, over all served positions, the widest
    gap of a served token below the reference's best (``program``) and
    the mean gap (``program_mean``); with ``control``, the same of the
    control's first choice (``control``, ``control_mean``); each
    sample's own readings (``rows``, by the same keys); and the number of
    positions read."""
    keys = ("program", "control") if control else ("program",)
    worst = dict.fromkeys(keys, 0.0)
    total = dict.fromkeys(keys, 0.0)
    rows = {k + s: [] for k in keys for s in ("", "_mean")}
    positions = 0
    for i in range(0, len(samples), block_rows):
        block = samples[i:i + block_rows]
        toks = np.zeros((block_rows, length), np.int32)
        served = np.zeros((block_rows, length), np.int32)
        mask = np.zeros((block_rows, length), bool)
        for r, (prompt, out) in enumerate(block):
            seq = np.concatenate([prompt, out]).astype(np.int32)
            assert len(seq) <= length, (len(seq), length)
            toks[r, :len(seq)] = seq
            # logits at position j predict token j + 1
            p = len(prompt)
            served[r, p - 1:p - 1 + len(out)] = out
            mask[r, p - 1:p - 1 + len(out)] = True
        alt = (np.asarray(_control_choice(logits, m, w, toks)) if control
               else np.zeros_like(toks))
        gaps = dict(zip(("program", "control"),
                        (np.asarray(g, np.float64) for g in
                         _gaps(logits, m, w, toks, served, alt))))
        for k in keys:
            g = gaps[k]
            rows[k] += [float(g[r][mask[r]].max()) for r in range(len(block))]
            rows[k + "_mean"] += [float(g[r][mask[r]].mean())
                                  for r in range(len(block))]
            worst[k] = max(worst[k], float(g[mask].max()))
            total[k] += float(g[mask].sum())
        positions += int(mask.sum())
    out = {"rows": rows, "positions": positions}
    for k in keys:
        out[k] = worst[k]
        out[k + "_mean"] = total[k] / max(positions, 1)
    return out
