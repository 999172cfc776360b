"""segsum_roofline (%, device trace): the PageRank segment sum's share of
its bandwidth roofline over the kernel's calls in the traced part of the
window: the least time of each call (``costs.segment_sum_bytes`` of its
shapes over the chip's HBM bandwidth, ``peaks``) summed, over the calls'
device time summed.

The trace names the kernel by the Pallas call's HLO instruction,
``%segment_sum_sorted.N = f32[rows,D] custom-call(..., f32[E,D] ...)``,
inside the module ``jit_segment_sum_sorted``; E and D are read from the
message operand's shape there, the vertex count from the configuration.
Left out when the trace holds no such call."""
import re

from bench import costs, peaks

MODULES = [r"^jit_segment_sum_sorted$"]
KERNEL = [r"^%?segment_sum_sorted\.\d+$"]
SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")


def read(rec):
    if rec.trace is None:
        return None
    calls = rec.trace.matching(modules=MODULES, names=KERNEL)
    n = 1 << int(rec.cell.config["scale"])
    least = dur = 0.0
    bw = peaks.peaks(rec.device["kind"])["hbm_bytes_per_s"]
    for op in calls:
        shapes = SHAPE.findall(op.text)
        if len(shapes) < 2:  # output and message operand
            continue
        edges, lanes = (int(x) for x in shapes[1])
        least += costs.segment_sum_bytes(edges, lanes, n) / bw
        dur += op.dur_ns * 1e-9
    if not dur:
        return None
    return 100.0 * least / dur
