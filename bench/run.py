#!/usr/bin/env python3
"""The benchmark of the graph-streaming service, one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout on a machine with the TPU chips the cell
asks for; refuses any other platform.  Set-up (graph generation, host
C-tree, device mirror, compile-cache loads, warm-up of the cell's own
shapes) is timed as ``setup_s``; then the cell's traffic is offered for
``--seconds`` and its answers and writes are compared with the plain
reference (``reference.py``).  ``--trace 1`` profiles a steady part of the
window and reports the per-layer metrics instead of the end-to-end ones.

Progress, the set-up split, peak HBM bytes and the writer's backlog go to
standard error; its last lines are the numbers compared, each with its
limit.  The last line of standard output is the result object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; exits unless they are TPUs, at
    least ``chips`` of them, with published peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chips, found {len(devs)}")
    from bench import peaks

    peaks.peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def open_cell(workload: str, **log_kv):
    """Place the compile cache, import the program, find the cell and its
    chips; returns (the harness module, the cell, the device)."""
    # the persistent compile cache sits at a fixed path in the checkout
    # unless the environment names one; JAX reads the variable at import
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    import repro  # noqa: F401  (x64 on, as the program runs)
    from bench import harness
    from repro import compile_cache

    cell = harness.Cell(workload)
    device = device_info(cell.chips)
    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log("run", workload=workload, device=json.dumps(device),
                compile_cache=cache, **log_kv)
    return harness, cell, device


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        print(f"bench: the program (src/repro) is missing under {ROOT}", file=sys.stderr)
        return 2
    harness, cell, device = open_cell(args.workload, seed=args.seed,
                                      seconds=args.seconds, trace=args.trace)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, device=device)
    for name, c in out["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
