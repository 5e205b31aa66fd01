"""RGA list linearization kernel, slope-measured (ISSUE 14).

Same protocol as bench.py: the kernel runs inside a fused fori_loop at
two iteration counts; the slope between the two wall times cancels the
fixed per-dispatch cost (its size on the attached chip: not
measured), and EVERY
kernel output folds into the checksum carry so XLA cannot DCE a stage
(the r2/r3 lesson). The per-iteration perturbation here must keep the
input a VALID forest, so the loop alternates between two precomputed
random forests on the same cells — the positions genuinely change
every iteration and neither structure can be hoisted.

Measures, at N elements over K cells (tombstone ratio ~50%):
- **linearize**: the full device twin (`rga_order_core`) — one packed
  (cell | parent | rank) sort, Euler-tour predecessor construction,
  log2(2N) pointer-jumping gathers, then the second sort + segmented
  alive-slot scan on the shared `pallas_scan` machinery.
- **host_oracle**: the pure-Python `crdt_list.linearize` replay on the
  same shape — the honest CPU baseline the device path has to beat.

`--smoke` runs a small shape, asserts bit-parity against the host
oracle per cell (positions AND alive slots), and prints the same JSON
line (CI). Prints ONE JSON line.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

ITERS_LO, ITERS_HI = 2, 10


def _slope(run, iters_lo=ITERS_LO, iters_hi=ITERS_HI, reps=3):
    """Per-iteration seconds via the two-count slope, best of reps."""
    run(iters_lo)  # compile both shapes before timing
    run(iters_hi)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        run(iters_lo)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(iters_hi)
        t_hi = time.perf_counter() - t0
        s = (t_hi - t_lo) / (iters_hi - iters_lo)
        best = s if best is None else min(best, s)
    return best


def _random_forest(n, k, seed):
    """(cell, parent, alive): contiguous cells, every parent an earlier
    element of the same cell or −1 (head) — a valid RGA forest in the
    kernel's sorted layout."""
    rng = np.random.default_rng(seed)
    cell = np.sort(rng.integers(0, k, n)).astype(np.int32)
    starts = np.r_[0, np.flatnonzero(np.diff(cell)) + 1]
    cell_start = np.repeat(starts, np.diff(np.r_[starts, n]))
    local = np.arange(n) - cell_start
    draw = np.floor(rng.random(n) * (local + 1)).astype(np.int64)
    parent = (cell_start - 1 + draw).astype(np.int32)
    parent = np.where(parent < cell_start, -1, parent).astype(np.int32)
    alive = rng.integers(0, 2, n).astype(np.int32)
    return cell, parent, alive


def bench_linearize(n, k):
    from evolu_tpu.ops.crdt_list_merge import rga_order_core

    cell, pa, alive = _random_forest(n, k, 5)
    _c2, pb, _a2 = _random_forest(n, k, 6)
    cell_j = jnp.asarray(cell)
    pa_j, pb_j = jnp.asarray(pa), jnp.asarray(pb)
    alive_j = jnp.asarray(alive)

    @functools.partial(jax.jit, static_argnums=0)
    def loop(iters):
        def body(i, acc):
            # Alternate between two valid forests: the tree structure —
            # and therefore every position — really changes each
            # iteration, so neither sort nor the ranking can be cached
            # out of the timed graph.
            par = jnp.where(i % 2 == 0, pa_j, pb_j)
            pos, slot = rga_order_core(cell_j, par, alive_j)
            # Consume EVERY output (slot is −1 for tombstones; +1 keeps
            # the sum sensitive to each one).
            return acc + pos.astype(jnp.uint64).sum() \
                + (slot + 1).astype(jnp.uint64).sum()

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.uint64))

    checks = {}

    def run(iters):
        checks[iters] = int(jax.block_until_ready(loop(iters)))

    s = _slope(run)
    # Liveness: different iteration counts must yield different carries.
    assert checks[ITERS_LO] != checks[ITERS_HI], "checksum carry is dead"
    return {"slope_ms": s * 1e3, "elems_per_s": n / s, "checksum": checks[ITERS_HI]}


def bench_host_oracle(n, k):
    from evolu_tpu.core import crdt_list as cl

    cell, parent, _alive = _random_forest(n, k, 5)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for c in range(k):
            lo, hi = np.searchsorted(cell, c), np.searchsorted(cell, c + 1)
            if lo == hi:
                continue
            tags = [f"{i:08d}" for i in range(lo, hi)]
            origins = ["" if parent[i] < 0 else f"{parent[i]:08d}"
                       for i in range(lo, hi)]
            cl.linearize(tags, origins)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return {"wall_ms": best * 1e3, "elems_per_s": n / best}


def parity_check(n=20_000, k=64):
    """Device/host bit-parity on random forests (the smoke gate):
    positions AND alive slots, per cell."""
    from evolu_tpu.core import crdt_list as cl
    from evolu_tpu.ops.crdt_list_merge import rga_order

    cell, parent, alive = _random_forest(n, k, 3)
    pos, slot = rga_order(cell, parent, alive)
    for c in range(k):
        lo, hi = np.searchsorted(cell, c), np.searchsorted(cell, c + 1)
        if lo == hi:
            continue
        tags = [f"{i:08d}" for i in range(lo, hi)]
        origins = ["" if parent[i] < 0 else f"{parent[i]:08d}"
                   for i in range(lo, hi)]
        expect = cl.linearize(tags, origins)
        assert list(pos[lo:hi]) == expect, f"pos parity broke in cell {c}"
        by_pos = sorted(range(lo, hi), key=lambda i: pos[i])
        s = 0
        for i in by_pos:
            if alive[i]:
                assert slot[i] == s, f"slot parity broke in cell {c}"
                s += 1
            else:
                assert slot[i] == -1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + host-oracle parity gate (CI)")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    n = args.n or (1 << 14 if args.smoke else 1 << 20)
    k = 1 << 6 if args.smoke else 1 << 12
    parity_check()
    out = {
        "bench": "crdt_list",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "n_elems": n,
        "cells": k,
        "smoke": bool(args.smoke),
        "linearize": bench_linearize(n, k),
        "host_oracle": bench_host_oracle(min(n, 1 << 17), min(k, 1 << 9)),
        "parity": "ok",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
