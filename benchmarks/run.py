"""Benchmark entrypoint: one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only T2,T7,...]
                                            [--json out.json]

Prints ``name,value,unit,notes`` CSV and a summary block comparing
measured ratios against the paper's claimed ranges.  ``--json`` also
writes the rows as a JSON list (one object per row) so CI runs can
archive the measurement trajectory across commits.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    from benchmarks.tables import ALL_BENCHES

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--json", default=None, help="also write rows as JSON here")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()

    only = set(args.only.split(",")) if args.only else None
    print("name,value,unit,notes")
    claims = []
    all_rows = []
    for name, fn in ALL_BENCHES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"{name},NaN,error,{type(e).__name__}: {e}", flush=True)
            continue
        for rname, value, unit, notes in rows:
            print(f"{rname},{value:.6g},{unit},{notes}", flush=True)
            all_rows.append(
                {"name": rname, "value": value, "unit": unit, "notes": notes}
            )
            if "paper:" in notes:
                claims.append((rname, value, notes))
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_rows, f, indent=1)
        print(f"# wrote {len(all_rows)} rows to {args.json}")

    if claims:
        print("#\n# --- paper-claim checkpoints ---")
        for rname, value, notes in claims:
            print(f"# {rname}: measured {value:.3g} ({notes})")


if __name__ == "__main__":
    main()
