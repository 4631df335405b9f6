"""Operations and bytes that the served work needs, from shapes alone.

Every family's counts (``families/<family>.py``: ``prefill_flops``,
``decode_flops``, ``kernels``) count what the algorithm needs, not what
today's program does: a prefill's real prompt tokens (no padding) and
logits at the last position only; a decode step's live requests at their
real KV lengths; a kernel's reads of the valid K/V rows only.  A change
that stops padding or copying dead blocks can then raise a share without
ever reading over 100%.
"""

from __future__ import annotations


def chunk_kv_lens(work):
    """Valid KV lengths at every step of a decode chunk, from the
    recorder's (KV length before the chunk, steps made) per live slot."""
    for base, steps in work:
        for j in range(1, steps + 1):
            yield base + j
