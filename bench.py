"""Benchmark: CRDT messages merged/sec/chip (BASELINE.json metric).

Measures the device merge pipeline that replaces the reference's
per-message applyMessages loop (SURVEY.md §2.3): batched LWW planning
(sort + segmented scans) + per-(owner, minute) Merkle XOR deltas +
batch digest, on a 1M-message batch spread over 1k owners with cell
contention (the config-3 shape). Inputs are device-resident columnar
arrays — the framework's device cell-version-cache design keeps them
there between batches (SURVEY.md §7, "hard parts" #4).

North star (BASELINE.json): ≥50M msgs/sec on v5e-4 = 12.5M/sec/chip;
`vs_baseline` reports the fraction of that per-chip target.

A device rate is only written from a device run: `main()` refuses any
backend but a TPU (exit code 2, no rate printed), and the result names
the platform, device kind and device count it ran on. Tests import
`make_loop` / `build_columns` / `shard_layout` on the CPU for the
liveness fences (tests/test_bench_liveness.py); they never time.

Prints exactly one JSON line.
"""

import json
import statistics
import sys
import time

import jax

import numpy as np

N = 1_000_000
OWNERS = 1_000
TARGET_PER_CHIP = 12_500_000.0


def build_columns(n=N, owners=OWNERS, seed=7, stored_winners=False):
    rng = np.random.default_rng(seed)
    base = 1_700_000_000_000
    # ~4 messages/cell contention, clustered minutes (realistic sync bursts).
    cells = max(n // 4, 1)
    cell_id = rng.integers(0, cells, n).astype(np.int32)
    owner_of_cell = rng.integers(0, owners, cells).astype(np.int64)
    owner_ix = owner_of_cell[cell_id]
    millis = base + rng.integers(0, 86_400_000, n).astype(np.int64)
    counter = rng.integers(0, 256, n).astype(np.int32)
    node = rng.integers(1, 2**63, n).astype(np.uint64)
    k1 = (millis.astype(np.uint64) << np.uint64(16)) | counter.astype(np.uint64)
    ex_k1 = np.zeros(n, np.uint64)
    ex_k2 = np.zeros(n, np.uint64)
    if stored_winners:
        # ~60% of cells carry a winner persisted by prior batches, drawn
        # from the same time window — so roughly half the incoming
        # messages LOSE to the stored winner, exercising both arms of
        # the _lex_max(p, e) seed and the `beats` compare (merge.py),
        # which the all-zero sentinel never touches.
        has = rng.random(cells) < 0.6
        w_millis = (base + rng.integers(0, 86_400_000, cells)).astype(np.uint64)
        w_k1 = ((w_millis << np.uint64(16)) | rng.integers(0, 256, cells).astype(np.uint64))
        w_k2 = rng.integers(1, 2**63, cells).astype(np.uint64)
        ex_k1 = np.where(has, w_k1, 0)[cell_id].astype(np.uint64)
        ex_k2 = np.where(has, w_k2, 0)[cell_id].astype(np.uint64)
    return {
        "cell_id": cell_id,
        "k1": k1,
        "k2": node,
        "ex_k1": ex_k1,
        "ex_k2": ex_k2,
        "millis": millis,
        "counter": counter,
        "node": node,
        "owner_ix": owner_ix,
    }


def shard_layout(cols, n_dev):
    """Repack flat columns so every owner's rows are contiguous inside
    exactly one equal-size shard chunk (the kernel's owner-locality
    precondition): owner → shard by owner % n_dev, each chunk padded to
    the max shard load rounded up to a power of two (pad rows carry the
    planner's padding cell and zero keys)."""
    n = len(cols["owner_ix"])
    shard_of = cols["owner_ix"] % n_dev
    order = np.argsort(shard_of, kind="stable")
    loads = np.bincount(shard_of, minlength=n_dev)
    chunk = 64
    while chunk < loads.max():
        chunk *= 2
    total = n_dev * chunk
    out = {}
    pad_cell = np.int32(0x7FFFFFFF)
    for k, v in cols.items():
        dst = np.zeros(total, v.dtype)
        if k == "cell_id":
            dst[:] = pad_cell
        start = 0
        for d in range(n_dev):
            rows = order[start : start + loads[d]]
            dst[d * chunk : d * chunk + loads[d]] = v[rows]
            start += loads[d]
        out[k] = dst
    return out, total


# Two fused-iteration counts per dispatch. Every dispatch carries a
# fixed cost (launch, argument handling, the result pull; its size on
# the attached chip: not measured) — dividing one wall time by its
# iteration count buries that cost in the per-iteration figure. The
# SLOPE between two iteration counts cancels the fixed term exactly:
# per_iter = (t_hi - t_lo) / (ITERS_HI - ITERS_LO). Inputs are
# perturbed per iteration so XLA cannot CSE, and the checksum carry
# keeps every iteration live.
ITERS_LO = 8
ITERS_HI = 72


def make_loop(mesh, iters, kernel=None):
    """The timed graph: `iters` fused reconcile iterations whose carry
    folds EVERY kernel output (the DCE fence — see body comments).
    Module-level so tests/test_bench_liveness.py can assert, output by
    output, that the checksum really depends on each pipeline stage;
    `kernel` is injectable for exactly that perturbation test."""
    import jax.numpy as jnp

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if kernel is None:
        from evolu_tpu.parallel.reconcile import _shard_kernel as kernel

    spec = P("owners")
    pad_cell = jnp.int32(0x7FFFFFFF)

    def shard_loop(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
        def body(i, acc):
            # Perturb per iteration so XLA cannot CSE iterations:
            # the HLC tie-break key flips low node bits, and the
            # cell ids are bijectively relabeled (cells < 2^18, so
            # XOR-ing bits 18+ keeps groups intact but reshuffles
            # the sort order — each iteration does real, different
            # data movement). Padding rows keep the sentinel cell.
            cid = jnp.where(
                cell_id == pad_cell, cell_id, cell_id ^ (i << 18).astype(jnp.int32)
            )
            outs = kernel(
                cid, k1, k2 ^ i.astype(jnp.uint64), ex_k1, ex_k2, owner_ix,
            )
            # Fold EVERY output into the carry so no stage of the
            # pipeline is dead code — consuming only the masks let
            # XLA DCE the whole Merkle minute-segment stage in
            # r2/r3 early runs (the digest doesn't depend on it),
            # silently flattering the number. psum replicates the
            # carry across shards. tests/test_bench_liveness.py fails
            # if any output stops feeding the checksum.
            local = outs[0].astype(jnp.int64).sum()
            for o in outs[1:-1]:
                local = local + o.astype(jnp.int64).sum()
            masked = jax.lax.psum(local, "owners")
            return acc + masked + outs[-1].astype(jnp.int64)

        return jax.lax.fori_loop(0, iters, body, jnp.int64(0))

    return jax.jit(
        shard_map(
            shard_loop,
            mesh=mesh,
            in_specs=(spec,) * 6,
            out_specs=P(),
            check_vma=False,
        )
    )


def main():
    if jax.default_backend() != "tpu":
        # No fallback that hides the device: a CPU wall under this
        # metric's name would be read as a chip number.
        print(
            f"bench.py measures the TPU and found backend "
            f"{jax.default_backend()!r}; refusing to print a rate",
            file=sys.stderr,
        )
        return 2

    from evolu_tpu.parallel.mesh import create_mesh, sharding

    mesh = create_mesh()  # all local devices
    n_dev = mesh.devices.size
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")

    results = {}
    with jax.enable_x64(True):
        loops = {k: make_loop(mesh, k) for k in (ITERS_LO, ITERS_HI)}
        for label, stored in (("empty_store", False), ("stored_winners", True)):
            cols, _ = shard_layout(build_columns(stored_winners=stored), n_dev)
            args = [jax.device_put(cols[k], shd) for k in names]
            medians = {}
            for iters, looped in loops.items():
                np.asarray(looped(*args))  # compile + warm
                times = []
                for _ in range(8):
                    t0 = time.perf_counter()
                    np.asarray(looped(*args))
                    times.append(time.perf_counter() - t0)
                medians[iters] = statistics.median(times)
            per_iter = (medians[ITERS_HI] - medians[ITERS_LO]) / (ITERS_HI - ITERS_LO)
            fixed = medians[ITERS_LO] - ITERS_LO * per_iter
            results[label] = {
                "per_chip": N / per_iter / n_dev,
                "per_iter_ms": round(per_iter * 1e3, 3),
                "dispatch_overhead_ms": round(fixed * 1e3, 1),
                "p50_ms_hi": round(medians[ITERS_HI] * 1e3, 3),
                "wall_per_chip_hi": round(ITERS_HI * N / medians[ITERS_HI] / n_dev),
            }

    # Headline = the stored-winners config: every kernel branch live
    # (winner-compare against a populated store, cells relabeled per
    # iteration). The empty-store config is reported alongside.
    head = results["stored_winners"]["per_chip"]
    print(
        json.dumps(
            {
                "metric": "crdt_messages_merged_per_sec_per_chip",
                "value": round(head),
                "unit": "msgs/sec/chip",
                "vs_baseline": round(head / TARGET_PER_CHIP, 4),
                "detail": {
                    "batch": N,
                    "owners": OWNERS,
                    "devices": n_dev,
                    "iters": [ITERS_LO, ITERS_HI],
                    "method": "two-point slope (fixed dispatch overhead cancelled)",
                    "stored_winners": True,
                    "rotating_cells": True,
                    "configs": {
                        k: {**v, "per_chip": round(v["per_chip"])}
                        for k, v in results.items()
                    },
                    "platform": mesh.devices.flat[0].platform,
                    "device_kind": mesh.devices.flat[0].device_kind,
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    # Global, not scoped: the whole pipeline is u64-keyed. Set only when
    # run as a script — tests import this module, and flipping the
    # process-wide default there would mask missing scoped
    # `with jax.enable_x64(True)` wraps in runtime code.
    jax.config.update("jax_enable_x64", True)
    sys.path.insert(0, ".")
    sys.exit(main())
