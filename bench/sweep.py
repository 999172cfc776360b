#!/usr/bin/env python3
"""Find the knee of a query cell once, on the chip: the highest offered
rate the service sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 10,20,40

Builds the cell once, then offers its traffic at each rate in turn for
``--seconds`` (the writer runs in every window) and prints one JSON line
per rate: queries offered and answered in the window, the p50 and p95
latency from when each query was due, the answers still outstanding at
the close, and how late the generator ran.  A rate is sustained while
nearly every query is answered inside its window and nothing piles up.
The cell's traffic file then fixes its rate at about four fifths of the
knee; the benchmark itself never searches.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import open_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    harness, cell, device = open_cell(args.workload)
    import numpy as np

    from bench import reference as R

    rec = harness.Record(cell, args.seed, args.seconds, False)
    graph, stream, svc = harness.build(cell, args.seed, rec)
    state = R.EdgeState(graph.n, graph.edges, graph.weights)
    gen_mod = cell.generator
    with svc:
        warm = gen_mod.plan(cell.traffic, graph, args.seed, args.seconds)
        gen_mod.warm(warm, svc, stream, state)
        harness.log("sweep", setup_s=time.perf_counter() - T_START, device=json.dumps(device))
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, queries=dict(cell.traffic["queries"], rate_per_s=rate))
            p = gen_mod.plan(traffic, graph, args.seed + i, args.seconds)
            r = harness.Record(cell, args.seed + i, args.seconds, False)
            gen_mod.drive(p, svc, stream, r, harness.Tracer(False))
            outstanding = sum(1 for q in p.queries
                              if q.ticket is not None and not q.ticket.done())
            gen_mod.settle(p, svc, r)
            lat = np.asarray([q.latency_s(r.t0) for q in p.queries]) * 1e3
            ok = np.isfinite(lat)
            print(json.dumps({
                "rate_per_s": rate, "offered": len(p.queries),
                "answered_in_window": int(sum(1 for q in p.queries
                                              if q.ok and q.done_t < r.t1)),
                "outstanding_at_close": outstanding,
                "failed": int((~ok).sum()),
                "p50_ms": float(np.percentile(lat[ok], 50)) if ok.any() else None,
                "p95_ms": float(np.percentile(lat[ok], 95)) if ok.any() else None,
                "generator_late_max_s": max(p.generator_late_s, default=0.0),
                "batch_mean": float(np.mean([q.batch for q in p.queries if q.batch])),
                "publishes": len(r.publishes),
            }), flush=True)
            if outstanding > 0.2 * len(p.queries):
                break  # past the knee: a higher rate only piles up more
    return 0


if __name__ == "__main__":
    sys.exit(main())
