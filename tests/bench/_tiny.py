"""Tiny versions of the benchmark's cells for CPU tests."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_cell(name, scale=9, rate=6.0, batch_pairs=32, period_s=1.0, **kw):
    """The cell ``name`` at 2^scale vertices with a short writer period."""
    cell = harness.Cell(name, **kw)
    cell.config = dict(cell.config, scale=scale)
    if cell.config.get("edge_capacity"):  # else the stream's default pool
        cell.config["edge_capacity"] = 1 << (scale + 5)
    writer = dict(cell.traffic["writer"], batch_pairs=batch_pairs, period_s=period_s)
    cell.traffic = dict(cell.traffic, writer=writer)
    if cell.traffic.get("queries"):
        cell.traffic["queries"] = dict(cell.traffic["queries"], rate_per_s=rate)
    return cell


def run_tiny(cell, seed=12345678901, seconds=3.0, traced=False, controls=False):
    return harness.run_cell(cell, seed, seconds, traced, device=CPU_DEVICE,
                            controls=controls)
