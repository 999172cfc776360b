"""Kernel block-shape autotuner: per-(backend, shape-bucket) winners.

The Pallas kernels expose their block shapes as static parameters
(``segment_reduce.segment_sum_sorted(edge_block=, dst_block=)``, the
csr_spmm tile sizes) but ``kernels/ops.py`` historically pinned the
module defaults.  The right shapes depend on the backend (CPU interpret
mode has no tiling cost model at all; on TPU the trade is VMEM residency
vs grid overhead) and on the problem shape — so dispatch consults this
table instead.

Design (DESIGN.md §12):

* **Cache key** = (kernel name, backend, sorted shape dims bucketed to
  the next power of two).  Bucketing keeps the table small and makes a
  whole stream of similar problem sizes hit one entry.
* **Process-level memo** — dispatch consults the table at Python trace
  time (block shapes are static arguments), and the memo guarantees
  exactly ONE consult per (kernel, backend, bucket): repeated dispatches
  are a dict hit (``CONSULTS`` counts the cold consults; tests spy it).
* **On-disk table** — set ``REPRO_AUTOTUNE_CACHE=/path/table.json`` to
  persist winners across processes (atomic tmp+rename writes, merged on
  load, corruption-tolerant).  Unset, the table is process-local only —
  the library never writes outside paths the user named.
* **Sweeping** runs real timings over ``CANDIDATES[kernel]`` and is OFF
  unless ``REPRO_AUTOTUNE=1`` asks for it, on any backend (interpret-mode
  timings on CPU measure the emulator, not the kernel — useful only as a
  smoke of the sweep machinery).  A sweep never runs while dispatch is
  under a trace (``traced=True``): there the thunks would return tracers
  and time the tracing, so a miss returns ``DEFAULTS[kernel]``.  With
  sweeping off, a miss returns ``DEFAULTS[kernel]`` too.  A sweep in
  which every candidate fails raises with the last error.  Invalidation
  is by key: a new jax backend or a different shape bucket is a
  different entry; bump ``TABLE_VERSION`` to invalidate a persisted
  table wholesale.

Every entry of ``DEFAULTS`` and ``CANDIDATES`` compiles for the TPU
(tests/test_chip_compile.py): the chunked kernels' edge block holds 32
whole chunk rows, the int8 sublane tile.

Callers pass a ``sweep_fn(params) -> thunk`` factory building the kernel
launch on synthetic inputs of the real shape; ``sweep`` times each
candidate (min over repeats, block_until_ready) and records the winner.
"""
from __future__ import annotations

import collections
import json
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

TABLE_VERSION = 1

DEFAULTS: Dict[str, Dict[str, int]] = {
    "segment_sum": {"edge_block": 512, "dst_block": 128},
    "segment_sum_weighted": {"edge_block": 512, "dst_block": 128},
    "segment_sum_chunked": {"edge_block": 4096, "dst_block": 128},
    "segment_sum_weighted_chunked": {"edge_block": 4096, "dst_block": 128},
    "spmm": {"row_tile": 128, "col_tile": 128},
}

# Small grids on purpose: every candidate costs a compile during a sweep.
# edge/dst blocks stay multiples of compressed.CHUNK (128) so the chunked
# kernels' whole-chunks-per-block invariant holds for every candidate, and
# chunked edge blocks are multiples of 32 rows * 128 (the int8 tile).
CANDIDATES: Dict[str, List[Dict[str, int]]] = {
    "segment_sum": [
        {"edge_block": e, "dst_block": d}
        for e in (256, 512, 1024)
        for d in (128, 256)
    ],
    "segment_sum_chunked": [
        {"edge_block": e, "dst_block": d}
        for e in (4096, 8192)
        for d in (128, 256)
    ],
    "spmm": [{"row_tile": t, "col_tile": t} for t in (128, 256)],
}
CANDIDATES["segment_sum_weighted"] = CANDIDATES["segment_sum"]
CANDIDATES["segment_sum_weighted_chunked"] = CANDIDATES["segment_sum_chunked"]

_memo: Dict[Tuple, Dict[str, int]] = {}
# cold-consult spy: bumped once per key the first time dispatch asks
CONSULTS: collections.Counter = collections.Counter()
# test hook: when set, overrides CANDIDATES (e.g. pinned single-candidate
# grids for determinism tests)
_candidate_override: Optional[Dict[str, List[Dict[str, int]]]] = None


def _bucket(x: int) -> int:
    """Next power of two >= x (shape bucket)."""
    return 1 << max(0, int(x - 1).bit_length())


def cache_key(kernel: str, backend: str, shape: Dict[str, int]) -> Tuple:
    return (
        TABLE_VERSION,
        kernel,
        backend,
        tuple(sorted((k, _bucket(int(v))) for k, v in shape.items())),
    )


def _key_str(key: Tuple) -> str:
    ver, kernel, backend, dims = key
    dim_s = ",".join(f"{k}={v}" for k, v in dims)
    return f"v{ver}|{kernel}|{backend}|{dim_s}"


def cache_path() -> Optional[str]:
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def _load_disk() -> Dict[str, Dict[str, int]]:
    path = cache_path()
    if not path or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            table = json.load(f)
        return table if isinstance(table, dict) else {}
    except (OSError, ValueError):
        return {}  # corrupt/partial table == empty table


def _save_disk(key: Tuple, params: Dict[str, int]) -> None:
    path = cache_path()
    if not path:
        return
    table = _load_disk()  # merge-on-load: keep other processes' winners
    table[_key_str(key)] = params
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(table, f, indent=0, sort_keys=True)
        os.replace(tmp, path)  # atomic on POSIX
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def sweep_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE") == "1"


def candidates_for(kernel: str) -> List[Dict[str, int]]:
    if _candidate_override is not None and kernel in _candidate_override:
        return _candidate_override[kernel]
    return CANDIDATES[kernel]


def set_candidates(override: Optional[Dict[str, List[Dict[str, int]]]]) -> None:
    """Pin the candidate grids (tests: determinism under a known grid).
    Pass None to restore the built-in grids."""
    global _candidate_override
    _candidate_override = override


def reset() -> None:
    """Drop the process memo + consult counters (tests)."""
    _memo.clear()
    CONSULTS.clear()


def sweep(
    kernel: str,
    make_thunk: Callable[[Dict[str, int]], Callable[[], object]],
    key: Tuple,
    repeats: int = 3,
) -> Dict[str, int]:
    """Time every candidate and record the winner under ``key``.

    ``make_thunk(params)`` returns a 0-arg callable running the kernel on
    representative inputs; it may raise to veto a candidate (e.g. a block
    larger than the problem).  Timing is min-over-repeats of a
    block_until_ready'd call, after one warmup/compile call.  Raises if
    every candidate fails, chained to the last error.
    """
    best: Optional[Dict[str, int]] = None
    best_t = float("inf")
    last_err: Optional[Exception] = None
    for params in candidates_for(kernel):
        try:
            thunk = make_thunk(params)
            jax.block_until_ready(thunk())  # compile + warm
            t = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(thunk())
                t = min(t, time.perf_counter() - t0)
        except Exception as e:  # candidate infeasible for this shape/backend
            last_err = e
            continue
        if t < best_t:
            best, best_t = dict(params), t
    if best is None:
        raise RuntimeError(
            f"autotune: every candidate of {kernel} failed for {_key_str(key)}"
        ) from last_err
    _memo[key] = best
    _save_disk(key, best)
    return best


def get_params(
    kernel: str,
    shape: Dict[str, int],
    sweep_fn: Optional[Callable[[Dict[str, int]], Callable[[], object]]] = None,
    backend: Optional[str] = None,
    traced: bool = False,
) -> Dict[str, int]:
    """The dispatch entry point: winner for (kernel, backend, bucket).

    Order: process memo -> on-disk table -> sweep (if enabled, a
    ``sweep_fn`` is given and dispatch is not ``traced``) -> ``DEFAULTS``.
    Exactly one cold consult per key; everything after is a memo hit.  A
    traced miss that a later eager dispatch could sweep is not memoised.
    """
    backend = backend or jax.default_backend()
    key = cache_key(kernel, backend, shape)
    hit = _memo.get(key)
    if hit is not None:
        return hit
    CONSULTS[key] += 1
    params = _load_disk().get(_key_str(key))
    can_sweep = sweep_fn is not None and sweep_enabled()
    if params is None and can_sweep and not traced:
        return sweep(kernel, sweep_fn, key)
    if params is None:
        params = dict(DEFAULTS[kernel])
        if can_sweep:
            return params
    _memo[key] = params
    return params
