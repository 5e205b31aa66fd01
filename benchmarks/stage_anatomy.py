"""Self-ablating stage anatomy of the fused reconcile pipeline (ISSUE 16).

"Re-ablate stages after every restructure" (CLAUDE.md) as an artifact
instead of a ritual: this harness builds one stage-TRUNCATED timed
variant per device stage of the registry in `evolu_tpu/obs/anatomy.py`
(key_sort → plan_compare → hash_render → minute_fold → delta_encode —
each variant keeps every output produced so far), verifies per variant
that EVERY retained output feeds the checksum carry (the r2/r3 DCE
lesson: a dead output means XLA silently times a smaller pipeline),
slope-measures each variant between two fused iteration counts (never
wall/count — the fixed dispatch RTT buries the figure), and reports
per-stage marginal costs, shares of the full pipeline, and the priced
roofline floors from the registry's cost laws. The pull wave is
measured separately (per-wave slope of `to_host_many` on the real
9-output kernel) since it lives outside the fused loop.

The JSON line is the `anatomy` baseline artifact
(`docs/baselines/anatomy.<platform>.json` via
benchmarks/compare_baselines.py). Hard gates even under --smoke:
`liveness_pass` (bool), `registry_digest` (registry/cost-law
fingerprint from obs.anatomy), and `pipeline_digest` (jaxpr primitive
multiset of the full variant at a fixed probe shape) — so restructuring
the pipeline or re-pricing a law without re-recording the baseline
from a real run fails CI. Stage shares/slopes are tolerance-compared
(25%) on non-smoke checks.

Usage:
    python benchmarks/stage_anatomy.py            # full (seeds laws)
    python benchmarks/stage_anatomy.py --smoke    # CI: tiny N, gates hard
    python benchmarks/stage_anatomy.py | \
        python benchmarks/compare_baselines.py --update anatomy

Prints exactly one JSON line.
"""

import argparse
import json
import os
import statistics
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

import bench
from evolu_tpu.obs import anatomy
from evolu_tpu.ops import to_host_many
from evolu_tpu.ops.encode import timestamp_hashes, unpack_ts_keys
from evolu_tpu.ops.merge import masks_from_sorted_flags, winner_flags
from evolu_tpu.ops.merkle_ops import owner_minute_segments
from evolu_tpu.parallel.mesh import create_mesh, sharding
from evolu_tpu.parallel.reconcile import (
    _CELL_BITS,
    _PAD_OWNER,
    pack_owner_cell_key,
    xor_allreduce,
)

# The ablation order IS the registry order; the import-time assert
# below fails the harness (and its smoke CI step) the moment the
# registry and this builder drift apart.
DEVICE_STAGES = tuple(s.name for s in anatomy.STAGES if s.kind == "device")
_EXPECTED_ORDER = ("key_sort", "plan_compare", "hash_render",
                   "minute_fold", "delta_encode")
assert DEVICE_STAGES == _EXPECTED_ORDER, (
    f"registry device stages {DEVICE_STAGES} no longer match the "
    f"variant builder {_EXPECTED_ORDER} — update build_variant AND "
    f"re-record docs/baselines/anatomy.*.json"
)

# Outputs added by each stage (must mirror the registry declaration —
# asserted below) and the cumulative variant arity.
_STAGE_OUTPUTS = {s.name: s.outputs for s in anatomy.STAGES
                  if s.kind == "device"}


def variant_arity(upto: str) -> int:
    k = DEVICE_STAGES.index(upto) + 1
    return sum(len(_STAGE_OUTPUTS[s]) for s in DEVICE_STAGES[:k])


def stage_output_indices(stage: str):
    """Indices (into the variant output tuple) of the outputs ADDED by
    `stage` — the per-stage liveness fence perturbs exactly these."""
    lo = variant_arity(stage) - len(_STAGE_OUTPUTS[stage])
    return range(lo, variant_arity(stage))


def build_variant(upto: str):
    """The reconcile shard kernel truncated after `upto`, retaining
    EVERY output produced so far (liveness discipline: the timed loop
    folds all of them, so no earlier stage is ever dead code in a
    later variant). Stage bodies are verbatim the production pipeline:
    reconcile._shard_kernel for the first four stages,
    engine._compact_segments_tail's encode tail for the fifth. Must be
    traced under enable_x64(True)."""
    k = DEVICE_STAGES.index(upto) + 1
    active = frozenset(DEVICE_STAGES[:k])

    def kernel(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
        n = cell_id.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        a, b = winner_flags(k1, k2, ex_k1, ex_k2)
        key = pack_owner_cell_key(
            owner_ix, cell_id, idx, lo_bits=2,
            lo=(b.astype(jnp.int64) << jnp.int64(1)) | a.astype(jnp.int64),
        )
        key_s, s1, s2 = jax.lax.sort((key, k1, k2), num_keys=1, is_stable=False)
        outs = [key_s, s1, s2]
        if "plan_compare" in active:
            owner_s = (key_s >> jnp.int64(_CELL_BITS + 26)).astype(jnp.int32)
            i_s = ((key_s >> jnp.int64(2)) & jnp.int64((1 << 24) - 1)).astype(jnp.int32)
            a_s = (key_s & jnp.int64(1)) != 0
            b_s = (key_s & jnp.int64(2)) != 0
            real = owner_s != jnp.int32(_PAD_OWNER)
            xor_s, upsert_s = masks_from_sorted_flags(
                key_s >> jnp.int64(26), s1, s2, a_s, b_s, real
            )
            outs += [xor_s, upsert_s, i_s]
        if "hash_render" in active:
            millis_s, counter_s = unpack_ts_keys(s1)
            hashes = jnp.where(
                xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0)
            )
            digest = xor_allreduce(
                jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,))
            )
            outs += [hashes, digest]
        if "minute_fold" in active:
            owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted = (
                owner_minute_segments(owner_s, millis_s, hashes, xor_s)
            )
            outs += [owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted]
        if "delta_encode" in active:
            # engine._compact_segments_tail's encode tail (the compact
            # delta 16B/row wire form): pack owner<<32|minute, stable
            # float-segments-to-front sort, distinct-segment count.
            is_seg = seg_end & valid_sorted
            packed = (
                owner_sorted.astype(jnp.uint64) << jnp.uint64(32)
            ) | minute_sorted.astype(jnp.uint32).astype(jnp.uint64)
            _, packed_c, xor_c = jax.lax.sort(
                (~is_seg, packed, seg_xor), num_keys=1, is_stable=True
            )
            seg_count = jnp.sum(is_seg.astype(jnp.int32))
            outs += [packed_c, xor_c, seg_count]
        assert len(outs) == variant_arity(upto), (
            f"variant {upto}: {len(outs)} outputs vs registry "
            f"{variant_arity(upto)} — registry and builder drifted"
        )
        return tuple(outs)

    return kernel


def make_variant_loop(mesh, iters, kernel):
    """bench.make_loop generalized to variable arity: `iters` fused
    iterations whose carry folds EVERY variant output (inputs
    perturbed per iteration so XLA cannot CSE, exactly the bench's
    discipline)."""
    spec = P("owners")
    pad_cell = jnp.int32(0x7FFFFFFF)

    def shard_loop(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
        def body(i, acc):
            cid = jnp.where(
                cell_id == pad_cell, cell_id, cell_id ^ (i << 18).astype(jnp.int32)
            )
            outs = kernel(cid, k1, k2 ^ i.astype(jnp.uint64), ex_k1, ex_k2,
                          owner_ix)
            local = outs[0].astype(jnp.int64).sum()
            for o in outs[1:]:
                local = local + o.astype(jnp.int64).sum()
            return acc + jax.lax.psum(local, "owners")

        return jax.lax.fori_loop(0, iters, body, jnp.int64(0))

    return jax.jit(
        shard_map(shard_loop, mesh=mesh, in_specs=(spec,) * 6,
                  out_specs=P(), check_vma=False)
    )


def perturbing_kernel(base_kernel, j, arity):
    """The variant kernel with output j nudged by one unit/flip — the
    minimal observable change a live fold must propagate (the
    tests/test_bench_liveness.py pattern, arity-generic)."""

    def kernel(*args):
        outs = list(base_kernel(*args))
        assert len(outs) == arity, f"variant grew to {len(outs)} outputs"
        o = outs[j]
        if o.ndim == 0:
            outs[j] = ~o if o.dtype == jnp.bool_ else o + jnp.ones((), o.dtype)
        elif o.dtype == jnp.bool_:
            outs[j] = o.at[0].set(~o[0])
        else:
            outs[j] = o.at[0].add(jnp.ones((), o.dtype))
        return tuple(outs)

    return kernel


def liveness_check(mesh, args, upto: str):
    """Per-variant DCE fence: returns the list of output indices whose
    perturbation does NOT move the checksum (must be empty). iters=1 so
    a bool-flip delta cannot cancel across iterations."""
    kernel = build_variant(upto)
    arity = variant_arity(upto)
    base = int(make_variant_loop(mesh, 1, kernel)(*args))
    dead = []
    for j in range(arity):
        loop = make_variant_loop(mesh, 1, perturbing_kernel(kernel, j, arity))
        if int(loop(*args)) == base:
            dead.append(j)
    return dead


def _interleaved_samples(mesh, args, kernels, iters_pair, reps):
    """Wall-time samples for every (variant, iteration-count) pair,
    taken round-robin: compile everything first, then each rep round
    times all pairs back-to-back. Marginals are differences of slopes
    — on a shared 1-core box, minutes-apart slopes carry enough load
    drift to swamp any stage under ~300 ms/iter (three early runs put
    hash_render's marginal at 3, 105 and 119 ms). Interleaving puts
    the subtracted measurements seconds apart inside one rep round, so
    drift hits both sides of every difference."""
    loops = {}
    for name, kernel in kernels.items():
        for iters in iters_pair:
            loop = make_variant_loop(mesh, iters, kernel)
            np.asarray(loop(*args))  # compile + warm
            loops[(name, iters)] = loop
    samples = {key: [] for key in loops}
    for _ in range(reps):
        for key, loop in loops.items():
            t0 = time.perf_counter()
            np.asarray(loop(*args))
            samples[key].append(time.perf_counter() - t0)
    return samples


def _per_rep_slopes(samples, names, iters_pair, reps):
    """Per-rep two-point slopes (seconds/iter) per variant — the
    CLAUDE.md slope rule applied within each rep round."""
    lo, hi = iters_pair
    return {
        name: [
            (samples[(name, hi)][r] - samples[(name, lo)][r]) / (hi - lo)
            for r in range(reps)
        ]
        for name in names
    }


def measure_pull_wave(mesh, cols, wave_pair, reps):
    """Per-wave slope of `to_host_many` over the real 9-output kernel's
    device results (the wave lives OUTSIDE the fused loop, so it gets
    its own two-point measurement over wave counts)."""
    from evolu_tpu.parallel.reconcile import reconcile_columns_sharded

    outs = reconcile_columns_sharded(mesh, cols)
    wave_bytes = sum(int(a.nbytes) for a in to_host_many(*outs))  # warm
    lo, hi = wave_pair
    medians = {}
    for waves in wave_pair:
        runs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(waves):
                to_host_many(*outs)
            runs.append(time.perf_counter() - t0)
        medians[waves] = statistics.median(runs)
    per_wave_ms = (medians[hi] - medians[lo]) / (hi - lo) * 1e3
    mb = wave_bytes / 1e6
    return {
        "ms_per_wave": round(per_wave_ms, 4),
        "wave_mb": round(mb, 3),
        "mb_per_s": round(mb / (per_wave_ms / 1e3), 2) if per_wave_ms > 0 else 0.0,
    }


def _sub_jaxprs(v):
    vs = v if isinstance(v, (list, tuple)) else (v,)
    out = []
    for x in vs:
        if hasattr(x, "eqns"):
            out.append(x)
        elif hasattr(getattr(x, "jaxpr", None), "eqns"):
            out.append(x.jaxpr)
    return out


def _collect_prims(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _collect_prims(sub, acc)


def pipeline_fingerprint(mesh) -> str:
    """crc32 of the full variant's jaxpr primitive multiset at a FIXED
    probe shape (independent of run size, so smoke and full runs agree).
    A perf restructure of any stage changes the traced program →
    changes this digest → the baseline gate fails until the anatomy is
    re-recorded. Falls back to the string form if jaxpr internals move
    between jax versions."""
    import zlib

    n = mesh.devices.size * 64
    probe = (
        np.full(n, 0x7FFFFFFF, np.int32),      # cell_id: all padding
        np.zeros(n, np.uint64), np.zeros(n, np.uint64),
        np.zeros(n, np.uint64), np.zeros(n, np.uint64),
        np.zeros(n, np.int64),
    )
    loop = make_variant_loop(mesh, 1, build_variant(DEVICE_STAGES[-1]))
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(loop)(*probe)
    try:
        prims: list = []
        _collect_prims(jaxpr.jaxpr, prims)
        canon = ",".join(f"{k}:{v}" for k, v in sorted(Counter(prims).items()))
    except Exception:  # noqa: BLE001 - fingerprint, not correctness
        canon = str(jaxpr)
    return f"{zlib.crc32(canon.encode()) & 0xFFFFFFFF:08x}"


def run(n, owners, iters_pair, reps, wave_pair, liveness_n=512):
    mesh = create_mesh()
    n_dev = mesh.devices.size
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")

    with jax.enable_x64(True):
        # 1. Per-variant liveness fence at a tiny shape (the timing
        # would be a lie for any variant with a dead output).
        tiny_cols, _ = bench.shard_layout(
            bench.build_columns(n=liveness_n, owners=16, stored_winners=True),
            n_dev,
        )
        tiny_args = [jax.device_put(tiny_cols[k], shd) for k in names]
        dead_by_variant = {}
        for name in DEVICE_STAGES:
            dead = liveness_check(mesh, tiny_args, name)
            if dead:
                dead_by_variant[name] = dead
        liveness_pass = not dead_by_variant

        # 2. Slope-measure every truncated variant at the real shape.
        cols, _ = bench.shard_layout(
            bench.build_columns(n=n, owners=owners, stored_winners=True), n_dev
        )
        args = [jax.device_put(cols[k], shd) for k in names]
        samples = _interleaved_samples(
            mesh, args, {n_: build_variant(n_) for n_ in DEVICE_STAGES},
            iters_pair, reps,
        )
        rep_slopes = _per_rep_slopes(samples, DEVICE_STAGES, iters_pair, reps)
        slopes = {n_: statistics.median(s) * 1e3
                  for n_, s in rep_slopes.items()}
        # Marginal = median over reps of the WITHIN-REP slope
        # difference (drift-robust), not the difference of medians.
        marginals = {}
        prev_name = None
        for name in DEVICE_STAGES:
            if prev_name is None:
                diffs = rep_slopes[name]
            else:
                diffs = [a - b for a, b in
                         zip(rep_slopes[name], rep_slopes[prev_name])]
            marginals[name] = statistics.median(diffs) * 1e3
            prev_name = name
        lo = iters_pair[0]
        full_name = DEVICE_STAGES[-1]
        fixed_full = statistics.median(
            samples[(full_name, lo)][r] - lo * rep_slopes[full_name][r]
            for r in range(reps)
        ) * 1e3

        # 3. Pull wave (outside the fused loop).
        pull = measure_pull_wave(mesh, cols, wave_pair, reps)

    device = jax.devices()[0]
    kind = device.device_kind  # a device without laws raises: no silent 0
    full = slopes[DEVICE_STAGES[-1]]
    stages = {}
    for name in DEVICE_STAGES:
        marginal = marginals[name]
        floor = anatomy.floor_ms(name, rows=n, device_kind=kind)
        stages[name] = {
            "slope_ms": round(slopes[name], 4),
            "marginal_ms": round(marginal, 4),
            "share": round(max(marginal, 0.0) / full, 4) if full > 0 else 0.0,
            "floor_ms": None if floor is None else round(floor, 4),
            "floor_ratio": (
                round(max(marginal, 0.0) / floor, 3) if floor else None
            ),
        }
    pull_floor = anatomy.floor_ms(
        "pull_wave", nbytes=int(pull["wave_mb"] * 1e6), device_kind=kind)
    pull["floor_ms"] = None if pull_floor is None else round(pull_floor, 4)

    return {
        "metric": "stage_anatomy",
        "platform": device.platform,
        "device_kind": kind,
        "batch": n,
        "owners": owners,
        "devices": n_dev,
        "iters": list(iters_pair),
        "reps": reps,
        "liveness_pass": liveness_pass,
        "dead_outputs": dead_by_variant,
        "registry_digest": anatomy.registry_digest(),
        "pipeline_digest": pipeline_fingerprint(mesh),
        "full_pipeline_ms_per_iter": round(full, 4),
        "dispatch_fixed_ms": round(fixed_full, 3),
        "stages": stages,
        "pull_wave": pull,
        "method": "per-variant checksum-carry liveness fence, then "
                  "interleaved two-point slopes (all variants timed "
                  "round-robin per rep; fixed dispatch overhead "
                  "cancelled); marginal = median over reps of the "
                  "within-rep slope_k - slope_{k-1}; pull wave "
                  "slope-measured over wave counts",
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny N for CI: gates (liveness/digests) are "
                         "exercised for real, timings are advisory")
    ap.add_argument("--n", type=int, default=None,
                    help="batch rows (default: 2^19 full, 2^14 smoke)")
    args = ap.parse_args()
    if args.smoke:
        n = args.n or (1 << 14)
        rec = run(n, owners=64, iters_pair=(2, 6), reps=3, wave_pair=(1, 3))
    else:
        n = args.n or (1 << 19)
        rec = run(n, owners=512, iters_pair=(2, 10), reps=5, wave_pair=(2, 8))
    print(json.dumps(rec))
    return 0 if rec["liveness_pass"] else 1


if __name__ == "__main__":
    jax.config.update("jax_enable_x64", True)
    sys.exit(main())
