"""Least HBM bytes a kernel's work needs, from the cell's shapes.

Counted from what the work must read and write, not from the blocks one
implementation moves (padding, int32 masks, lane-dense copies), so a
replacement kernel is judged against the same work.  Values are float32
(4 bytes), flags one byte, a region id one byte.  Both kernels run at
d=2 and k<=4: a few operations per byte, far below the v5e's ridge point
(197e12 / 819e9 = 240 FLOP/byte), so the HBM bound is the roofline and no
FLOP count is kept.

Shapes: ``q`` tenants batched in one call, ``n`` peers, ``D`` slots per
peer, ``d`` dimensions; a weighted vector is ``d + 1`` floats.
"""

F32 = 4


def lss_state_bytes(q: int, n: int, D: int, d: int) -> int:
    """Status, agreements and Alg.-1 violations: reads the local input,
    the out- and in-messages and the live-slot flags; writes the status,
    the violation flags and the decision."""
    wv = d + 1
    reads = n * wv * F32 + 2 * n * D * wv * F32 + n * D
    writes = n * wv * F32 + n * D + n
    return q * (reads + writes)


def correction_bytes(q: int, n: int, D: int, d: int) -> int:
    """Eq.-10 corrected messages: reads the entry status, agreements,
    in-messages and violating set; writes the new out-messages."""
    wv = d + 1
    reads = n * wv * F32 + 2 * n * D * wv * F32 + n * D
    writes = n * D * wv * F32
    return q * (reads + writes)
