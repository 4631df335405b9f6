"""Operations and bytes that the served work needs, from shapes alone.

These count what the algorithm needs, not what today's program does: a
prefill's real prompt tokens (no padding) and logits at the last
position only; a decode step's live requests at their real KV lengths;
the ragged kernel's reads of the valid K/V rows only.  A change that
stops padding or copying dead blocks can then raise a share without
ever reading over 100%.
"""

from __future__ import annotations

from spec import ModelShape


def matmul_params(m: ModelShape) -> int:
    """Weights one token multiplies by in the layer stack."""
    attn = m.d * (m.heads + 2 * m.kv_heads) * m.head_dim \
        + m.heads * m.head_dim * m.d
    return m.layers * (attn + 3 * m.d * m.ffn)


def head_flops(m: ModelShape) -> int:
    return 2 * m.d * m.vocab


def attn_flops(m: ModelShape, n_q: int, n_kv: int) -> int:
    """QK^T and PV for ``n_q`` query positions against ``n_kv`` keys in
    one layer: 2 FLOPs per multiply-add, two products."""
    return 4 * m.heads * m.head_dim * n_q * n_kv


def prefill_flops(m: ModelShape, prompt_lens) -> int:
    """Causal prefill of each prompt, logits at its last position."""
    total = 0
    for p in prompt_lens:
        pairs = p * (p + 1) // 2
        total += 2 * matmul_params(m) * p + head_flops(m) \
            + m.layers * 4 * m.heads * m.head_dim * pairs
    return total


def decode_flops(m: ModelShape, kv_lens) -> int:
    """One decode step of each live request; ``kv_lens`` are the valid
    KV lengths its attention reads (the new token included)."""
    return sum(2 * matmul_params(m) + head_flops(m)
               + m.layers * attn_flops(m, 1, n) for n in kv_lens)


def ragged_kernel(m: ModelShape, kv_len: int, dtype_bytes: int = 2):
    """(FLOPs, bytes) of the ragged decode kernel for one request in one
    layer: q, the valid K and V rows, and the output."""
    flops = attn_flops(m, 1, kv_len)
    qo = 2 * m.heads * m.head_dim * dtype_bytes
    kv = 2 * kv_len * m.kv_heads * m.head_dim * dtype_bytes
    return flops, qo + kv


def chunk_kv_lens(work):
    """Valid KV lengths at every step of a decode chunk, from the
    recorder's (KV length before the chunk, steps made) per live slot."""
    for base, steps in work:
        for j in range(1, steps + 1):
            yield base + j
