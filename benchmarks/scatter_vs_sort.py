"""Sort vs scatter-argmax LWW plan, slope-measured (ISSUE 4).

Same protocol as bench.py (two fused iteration counts per dispatch;
the slope cancels fixed dispatch overhead; every kernel output folds
into the checksum carry so XLA cannot DCE a stage), over the config-3
shard layout on all local devices. The per-iteration perturbation
relabels cells BIJECTIVELY WITHIN the cell-id range (XOR of low bits)
instead of bench.py's high-bit XOR — the scatter kernel's winner table
is sized to the cell-id range, and letting the relabel escape it would
compare a 2^18-cell sort against a 2^25-slot table. Checksum parity
between the two kernels is asserted on the XOR digest (order-free);
the full mask/delta parity is test-pinned in
tests/test_scatter_merge.py.

Prints one JSON line.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

N = int(os.environ.get("SVS_N", 1_000_000))
OWNERS = 1_000
ITERS_LO, ITERS_HI = 2, 8


def make_loop(mesh, iters, kernel, cell_bits):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P("owners")
    pad_cell = jnp.int32(0x7FFFFFFF)
    low_mask = (1 << (cell_bits - 6)) - 1

    def shard_loop(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
        def body(i, acc):
            # Bijective in-range relabel: XOR the low cell bits with a
            # per-iteration pattern (cells stay < 2^cell_bits, so both
            # kernels see the same table/key bounds every iteration)
            # and flip HLC node bits so the compare order really moves.
            cid = jnp.where(
                cell_id == pad_cell,
                cell_id,
                cell_id ^ (i * jnp.int32(0x2B)) & jnp.int32(low_mask),
            )
            outs = kernel(cid, k1, k2 ^ i.astype(jnp.uint64), ex_k1, ex_k2, owner_ix)
            local = outs[0].astype(jnp.int64).sum()
            for o in outs[1:-1]:
                local = local + o.astype(jnp.int64).sum()
            masked = jax.lax.psum(local, "owners")
            return acc + masked + outs[-1].astype(jnp.int64)

        return jax.lax.fori_loop(0, iters, body, jnp.int64(0))

    return jax.jit(
        shard_map(shard_loop, mesh=mesh, in_specs=(spec,) * 6, out_specs=P(),
                  check_vma=False)
    )


def main():
    import bench
    from evolu_tpu.ops.merge import _PAD_CELL
    from evolu_tpu.ops.scatter_merge import table_size_for
    from evolu_tpu.parallel.mesh import create_mesh, sharding
    from evolu_tpu.parallel.reconcile import _shard_kernel, scatter_shard_kernel

    mesh = create_mesh()
    n_dev = mesh.devices.size
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")
    cols, _ = bench.shard_layout(
        bench.build_columns(n=N, owners=OWNERS, stored_winners=True), n_dev
    )
    real = cols["cell_id"] != int(_PAD_CELL)
    cell_max = int(cols["cell_id"].max(initial=0, where=real))
    table = table_size_for(cell_max)
    cell_bits = table.bit_length() - 1
    variants = {
        "sort": _shard_kernel,
        "scatter": scatter_shard_kernel(table),
    }
    results = {}
    digests = {}
    with jax.enable_x64(True):
        args = [jax.device_put(cols[k], shd) for k in names]
        for label, kernel in variants.items():
            medians = {}
            for iters in (ITERS_LO, ITERS_HI):
                loop = make_loop(mesh, iters, kernel, cell_bits)
                np.asarray(loop(*args))  # compile + warm
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    np.asarray(loop(*args))
                    times.append(time.perf_counter() - t0)
                medians[iters] = statistics.median(times)
            per_iter = (medians[ITERS_HI] - medians[ITERS_LO]) / (ITERS_HI - ITERS_LO)
            results[label] = {
                "per_iter_ms": round(per_iter * 1e3, 2),
                "per_chip": round(N / per_iter / n_dev),
            }
            # Order-free parity probe: the XOR digest of one plain
            # dispatch (the loop checksum itself is order-SENSITIVE in
            # the segment columns — tile-local grouping sees different
            # row orders per kernel — so cross-kernel equality is
            # asserted on the digest; full mask/delta parity is pinned
            # in tests/test_scatter_merge.py).
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            dig = jax.jit(shard_map(
                lambda *a: kernel(*a)[-1], mesh=mesh,
                in_specs=(P("owners"),) * 6, out_specs=P(), check_vma=False,
            ))
            digests[label] = int(np.asarray(dig(*args)))
    print(json.dumps({
        "metric": "scatter_vs_sort_plan",
        "n": N,
        "owners": OWNERS,
        "devices": n_dev,
        "platform": jax.devices()[0].platform,
        "cell_max": cell_max,
        "table_slots": table,
        "variants": results,
        "checksums_equal": digests["sort"] == digests["scatter"],
        "speedup_scatter_over_sort": round(
            results["sort"]["per_iter_ms"] / results["scatter"]["per_iter_ms"], 3
        ),
    }))


if __name__ == "__main__":
    main()
