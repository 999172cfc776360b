"""Operations and bytes a kernel call must do, counted from its shapes.

``segment_sum_bytes``: the sorted segment sum ``out[d, :] = sum of
msg[e, :] over edges e with dst[e] == d`` must read each edge's int32
segment index and its D float32 messages once, and write each of the n
output rows of D float32 once.  What implements the sum (the one-hot MXU
product of ``kernels/segment_reduce.py`` today) does not enter: its
operations are not work the sum needs, so they are not counted.
"""
from __future__ import annotations

INDEX_BYTES = 4  # int32 segment index per edge
VALUE_BYTES = 4  # float32 message and output element


def segment_sum_bytes(edges: int, lanes: int, n: int) -> int:
    """Least bytes moved by one segment sum of ``edges`` messages of
    ``lanes`` values each onto ``n`` rows."""
    return edges * INDEX_BYTES + edges * lanes * VALUE_BYTES + n * lanes * VALUE_BYTES
