"""Tensor-CRDT merge kernels, slope-measured (ISSUE 20).

Same protocol as bench.py / crdt_types.py: each kernel runs inside a
fused fori_loop at two iteration counts; the slope between the two
wall times cancels the fixed per-dispatch cost (its size on the
attached chip: not measured), and EVERY kernel output folds into the checksum carry so
XLA cannot DCE a stage (the r2/r3 lesson). A per-output drop probe
additionally proves each declared output actually moves the carry.

Measures, at N contributing ops over K cells of `width` elements:
- **cell_fold sum/max**: `tensor_cell_fold_core` — ONE packed
  cell|idx i64 sort + a single row-gather recovering the (n, width)
  matrix + ONE flattened segmented scan over all width element
  columns + dense scatter. The design bet this bench prices: the
  recorded v5e law charges ~0.75 ms per extra u64 sort payload at 1M,
  so a width-8 cell carried as payloads would pay O(width) sorts —
  the gather layout pays one sort + one gather regardless of width.
- **shard packed/wide**: `tensor_shard_sums_core` (owner|cell|idx
  packed key, the reconcile drain shape) and the wide-id fallback
  (owner as a gathered payload) — tensor widths exercise the wide
  path at production shapes, so both variants are priced.

Gates (hard-fail, run in --smoke too): device twins bit-identical to
the pure-numpy host oracle (`core/crdt_tensor.py`) for sum, mean and
max monoids, and both shard variants vs a numpy group-by — the same
parity the goldens pin in tests/test_crdt_tensor.py.

HONESTY (docs/BENCHMARKS.md): CPU numbers from the CI container are
recorded as CPU numbers; no rate for this shape has been measured on
the chip.
Prints ONE JSON line; numbers live in docs/BENCHMARKS.md.
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
WIDTH = 8


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


def bench_cell_fold(n, k, monoid):
    from evolu_tpu.ops.crdt_tensor_merge import tensor_cell_fold_core

    rng = np.random.default_rng(7)
    cell = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    contrib = jnp.asarray(
        rng.integers(0, 1 << 48, (n, WIDTH)).astype(np.uint64))
    low_mask = jnp.int32(k - 1)  # k is a power of two

    @functools.partial(jax.jit, static_argnums=0)
    def loop(iters):
        def body(i, acc):
            # Bijective in-range relabel + value twiddle: the fold's
            # input really changes every iteration, so no stage can be
            # hoisted or cached out of the timed graph.
            cid = cell ^ (i.astype(jnp.int32) * jnp.int32(0x2B) & low_mask)
            v = contrib + (i & jnp.int64(7)).astype(jnp.uint64)
            table = tensor_cell_fold_core(cid, v, table_size=k,
                                          width=WIDTH, monoid=monoid)
            return acc + table.sum()  # consume the ONLY output

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.uint64))

    checks = {}

    def run(iters):
        checks[iters] = int(jax.block_until_ready(loop(iters)))

    s = _slope(run)
    # Liveness: different iteration counts must yield different carries.
    assert checks[ITERS_LO] != checks[ITERS_HI], "checksum carry is dead"
    return {"slope_ms": s * 1e3, "elems_per_s": n * WIDTH / s,
            "checksum": checks[ITERS_HI]}


def bench_shard(n, k, variant):
    from evolu_tpu.ops.crdt_tensor_merge import (
        tensor_shard_sums_core, tensor_shard_sums_wide_core)

    rng = np.random.default_rng(11)
    owner_np = rng.integers(0, 64, n).astype(np.int32)
    # Globally interned cell ids (unique per owner — the wide
    # contract); the wide leg pushes them past the packed 2^25 budget.
    cell_np = (rng.integers(0, k, n) * 64 + owner_np).astype(np.int32)
    if variant == "wide":
        cell_np = cell_np + (1 << 26)
    core = tensor_shard_sums_core if variant == "packed" \
        else tensor_shard_sums_wide_core
    owner = jnp.asarray(owner_np)
    cell = jnp.asarray(cell_np)
    contrib = jnp.asarray(
        rng.integers(0, 1 << 48, (n, WIDTH)).astype(np.uint64))

    # Per-output drop probe: each declared core output must move the
    # carry — a checksum formula that ignored an output would let XLA
    # DCE that stage out of the timed graph (the r2/r3 bug class).
    outs = [np.asarray(o) for o in jax.jit(core)(owner, cell, contrib)]
    parts = [np.uint64(o.astype(np.uint64).sum()) for o in outs]
    full = np.uint64(0)
    for p in parts:
        full = full + p
    for i, p in enumerate(parts):
        assert full != full - p, f"{variant} output {i} is checksum-dead"

    @functools.partial(jax.jit, static_argnums=0)
    def loop(iters):
        def body(i, acc):
            v = contrib + (i & jnp.int64(7)).astype(jnp.uint64)
            res = core(owner, cell, v)
            local = jnp.zeros((), jnp.uint64)
            for o in res:  # consume EVERY output
                local = local + o.astype(jnp.uint64).sum()
            return acc + local

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.uint64))

    checks = {}

    def run(iters):
        checks[iters] = int(jax.block_until_ready(loop(iters)))

    s = _slope(run)
    assert checks[ITERS_LO] != checks[ITERS_HI], "checksum carry is dead"
    return {"slope_ms": s * 1e3, "elems_per_s": n * WIDTH / s,
            "checksum": checks[ITERS_HI]}


def parity_check(n=6_000, k=64):
    """Device twins bit-identical to the pure-numpy host oracle — the
    HARD gate (runs under --smoke too): a fast kernel that drifts by
    one bit would fork replicas forever."""
    from evolu_tpu.core import crdt_tensor as tz
    from evolu_tpu.ops.crdt_tensor_merge import (
        tensor_cell_folds, tensor_shard_sums)

    rng = np.random.default_rng(3)
    for type_string in ("tensor:sum:f32:8", "tensor:mean:f32:8",
                        "tensor:max:bf16:8"):
        cfg = tz.parse_tensor_type(type_string)
        cell = rng.integers(0, k, n).astype(np.int32)
        contrib = np.empty((n, cfg.size), np.uint64)
        counts = rng.integers(1, 9, n)
        for i in range(n):
            vals = (rng.random(cfg.size) * 60 - 30).astype(np.float32)
            payload = vals.astype(tz._np_dtype(cfg)).tobytes()
            if cfg.monoid == "max":
                contrib[i] = tz.monotone_key(cfg, payload).astype(np.uint64)
            else:
                c = counts[i] if cfg.monoid == "mean" else 1
                contrib[i] = tz.quantize(cfg, payload).view(np.uint64) \
                    * np.uint64(c)
        table = tensor_cell_folds(cell, contrib, k, cfg.monoid)
        host = np.zeros((k, cfg.size), np.uint64)
        if cfg.monoid == "max":
            np.maximum.at(host, cell, contrib)
        else:
            np.add.at(host, cell, contrib)
        assert np.array_equal(table, host), f"{type_string} parity"
    owner = rng.integers(0, 8, n).astype(np.int64)
    for variant, bump in (("packed", 0), ("wide", 1 << 26)):
        cell = (rng.integers(0, k, n) * 8 + owner + bump).astype(np.int64)
        contrib = rng.integers(0, 1 << 40, (n, 4)).astype(np.uint64)
        got = tensor_shard_sums(owner, cell, contrib)
        expect = {}
        for o, c, v in zip(owner, cell, contrib):
            key = (int(o), int(c))
            expect[key] = expect.get(key, np.zeros(4, np.uint64)) + v
        assert set(got) == set(expect), f"{variant} shard keys"
        for key in expect:
            assert np.array_equal(got[key], expect[key].view(np.int64)), \
                f"{variant} shard parity"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + host-oracle parity gate (CI)")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    n = args.n or (1 << 13 if args.smoke else 1 << 20)
    k = 1 << 8 if args.smoke else 1 << 15
    parity_check()
    out = {
        "bench": "tensor_merge",
        "platform": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "n_ops": n,
        "cells": k,
        "width": WIDTH,
        "smoke": bool(args.smoke),
        "cell_fold_sum": bench_cell_fold(n, k, "sum"),
        "cell_fold_max": bench_cell_fold(n, k, "max"),
        "shard_packed": bench_shard(n, k, "packed"),
        "shard_wide": bench_shard(n, k, "wide"),
        "parity": "ok",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
