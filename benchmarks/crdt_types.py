"""CRDT typed-column merge kernels, slope-measured (ISSUE 7).

Same protocol as bench.py: each kernel runs inside a fused fori_loop at
two iteration counts; the slope between the two wall times cancels the
fixed per-dispatch cost (its size on the attached chip: not
measured), and EVERY
kernel output folds into the checksum carry so XLA cannot DCE a stage
(the r2/r3 lesson, fenced by tests/test_bench_liveness.py for the LWW
kernels; the same per-iteration perturbation discipline applies here).

Measures, at N ops over K cells:
- **counter**: the PN-counter fold (`pn_counter_sums_core`) — packed
  cell|idx sort + two segmented sums + dense scatter of per-cell
  totals. The sort-based shape, comparable row-for-row to the LWW sort
  plan's numbers in docs/BENCHMARKS.md.
- **awset**: the AW-set membership fold (`_killed_table_core` +
  `awset_pair_alive_core`) — pure scatter-OR, the shape where scatter
  has NO LWW duplicate-screen caveat. On CPU this is the plan that won
  PR 4; on TPU the recorded v5e law prices serialized scatters above a
  sort — whatever the chip says is recorded honestly.

`--smoke` runs a small shape, asserts bit-parity against the host
oracle (core/crdt_types.py), and prints the same JSON line (CI).
Prints ONE JSON line.
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


def bench_counter(n, k):
    from evolu_tpu.ops.crdt_merge import pn_counter_sums_core

    rng = np.random.default_rng(7)
    cell = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    delta = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int64))
    low_mask = jnp.int32(k - 1)  # k is a power of two

    @functools.partial(jax.jit, static_argnums=0)
    def loop(iters):
        def body(i, acc):
            # Bijective in-range relabel + delta twiddle: the fold's
            # input really changes every iteration, so no stage can be
            # hoisted or cached out of the timed graph.
            cid = cell ^ (i * jnp.int32(0x2B) & low_mask)
            d = delta + (i & jnp.int64(7))
            pos, neg = pn_counter_sums_core(cid, d, table_size=k)
            return acc + pos.sum() + neg.sum()  # consume EVERY output

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.uint64))

    checks = {}

    def run(iters):
        checks[iters] = int(jax.block_until_ready(loop(iters)))

    s = _slope(run)
    # Liveness: different iteration counts must yield different carries.
    assert checks[ITERS_LO] != checks[ITERS_HI], "checksum carry is dead"
    return {"slope_ms": s * 1e3, "ops_per_s": n / s, "checksum": checks[ITERS_HI]}


def bench_awset(n, k):
    from evolu_tpu.ops.crdt_merge import _killed_table_core, awset_pair_alive_core

    rng = np.random.default_rng(11)
    n_kills = n // 5
    pair = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    tag = jnp.asarray(np.arange(n, dtype=np.int32))
    kills = jnp.asarray(rng.integers(0, n, n_kills).astype(np.int32))
    mask = jnp.int32(n - 1)  # n is a power of two

    @functools.partial(jax.jit, static_argnums=0)
    def loop(iters):
        def body(i, acc):
            k_ids = kills ^ (i * jnp.int32(0x5D) & mask)
            killed = _killed_table_core(k_ids, num_tags=n)
            alive = jnp.int32(1) - killed[tag]
            member = awset_pair_alive_core(pair, alive, num_pairs=k)
            local = killed.sum() + alive.sum() + member.sum()
            return acc + local.astype(jnp.int64)

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.int64))

    checks = {}

    def run(iters):
        checks[iters] = int(jax.block_until_ready(loop(iters)))

    s = _slope(run)
    assert checks[ITERS_LO] != checks[ITERS_HI], "checksum carry is dead"
    return {"slope_ms": s * 1e3, "ops_per_s": n / s, "checksum": checks[ITERS_HI]}


def parity_check(n=20_000, k=128):
    """Host-oracle bit-parity on a random log (the smoke gate)."""
    from evolu_tpu.core import crdt_types as ct
    from evolu_tpu.ops import crdt_merge as cm

    rng = np.random.default_rng(3)
    cell = rng.integers(0, k, n).astype(np.int32)
    delta = rng.integers(-1000, 1000, n).astype(np.int64)
    pos, neg = cm.pn_counter_sums(cell, delta, k)
    hp = np.zeros(k, np.int64)
    hn = np.zeros(k, np.int64)
    np.add.at(hp, cell, np.where(delta > 0, delta, 0))
    np.add.at(hn, cell, np.where(delta < 0, -delta, 0))
    assert np.array_equal(pos, hp) and np.array_equal(neg, hn), "counter parity"
    tags = [f"t{i}" for i in range(2000)]
    kills = {t for i, t in enumerate(tags) if i % 3 == 0}
    state = {t for i, t in enumerate(tags) if i % 7 == 0}
    assert ct.alive_add_flags(tags, kills, state) == cm.awset_alive_flags(
        tags, kills, state
    ), "awset parity"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + host-oracle parity gate (CI)")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    n = args.n or (1 << 14 if args.smoke else 1 << 20)
    k = 1 << 10 if args.smoke else 1 << 18
    parity_check()
    out = {
        "bench": "crdt_types",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "n_ops": n,
        "cells": k,
        "smoke": bool(args.smoke),
        "counter": bench_counter(n, k),
        "awset": bench_awset(n, k),
        "parity": "ok",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
