"""Cell-range sharding for one hot owner.

`parallel.reconcile` never splits an owner across shards — right for
fleets of owners, wrong when ONE owner's batch exceeds a single
device. Per-cell LWW merges are independent, so a hot owner's batch
shards by cell id instead (SURVEY.md §5: "within one hot owner, by
cell-id ranges after the radix sort"): each device plans a contiguous
range of interned cell ids, per-minute Merkle XOR deltas are computed per shard and
XOR-combined across shards (XOR is associative/commutative, so
per-shard per-minute partial deltas merge exactly), and the batch
digest is XOR-allreduced over ICI.

Contract matches the single-device planner: masks in original batch
order, {base3-minute-key: delta} dict, uint32 digest.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import functools

from evolu_tpu.ops import bucket_size, to_host_many, with_x64
from evolu_tpu.ops.encode import timestamp_hashes, unpack_ts_keys
from evolu_tpu.ops.merge import _PAD_CELL, plan_merge_sorted_core, unpermute_masks
from evolu_tpu.ops.merkle_ops import decode_owner_minute_deltas, owner_minute_segments
from evolu_tpu.parallel.mesh import OWNERS_AXIS, put_sharded, require_single_process, sharding
from evolu_tpu.parallel.reconcile import xor_allreduce
from evolu_tpu.utils.log import span


def _shard_kernel(cell_id, k1, k2, ex_k1, ex_k2):
    xor_s, upsert_s, i_s, s1, s2, _ = plan_merge_sorted_core(cell_id, k1, k2, ex_k1, ex_k2)
    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0))
    # hi key = 0 for every real row (single owner); segments = minutes.
    zero_owner = jnp.zeros((), jnp.int32)
    _, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        zero_owner, millis_s, hashes, xor_s
    )
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid_sorted, digest


@functools.lru_cache(maxsize=None)
def _compiled_kernel(mesh: Mesh):
    spec = P(OWNERS_AXIS)
    return jax.jit(
        shard_map(
            _shard_kernel,
            mesh=mesh,
            in_specs=(spec,) * 5,
            out_specs=(spec,) * 7 + (P(),),
            check_vma=False,
        )
    )


@with_x64
def reconcile_hot_owner(
    mesh: Mesh,
    cell_id: np.ndarray,
    k1: np.ndarray,
    k2: np.ndarray,
    ex_k1: np.ndarray,
    ex_k2: np.ndarray,
    millis: np.ndarray,
    counter: np.ndarray,
    node: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, Dict[str, int], int]:
    """One owner's columnar batch, cells sharded over the mesh.

    Returns (xor_mask, upsert_mask, minute_deltas, digest) with masks in
    original batch order — identical to running `plan_merge_core` +
    minute deltas on one device (property-tested in tests).
    """
    require_single_process("reconcile_hot_owner")
    n = len(cell_id)
    n_dev = mesh.devices.size
    with span("kernel:reconcile", "reconcile_hot_owner", n=n, devices=n_dev):
        # Assign cells (not rows) to shards so every message of a cell
        # lands on the same device. Interned cell ids are dense
        # (0..num_cells-1), so contiguous ranges balance well.
        num_cells = int(cell_id.max()) + 1 if n else 1
        shard_of = (cell_id.astype(np.int64) * n_dev) // num_cells
        order = np.argsort(shard_of, kind="stable")
        loads = np.bincount(shard_of, minlength=n_dev)
        chunk = bucket_size(int(loads.max()) if n else 1)
        total = n_dev * chunk

        # millis/counter/node are recovered on device from the HLC keys;
        # only the key columns are laid out and transferred.
        cols = {
            "cell_id": np.full(total, int(_PAD_CELL), np.int32),
            "k1": np.zeros(total, np.uint64),
            "k2": np.zeros(total, np.uint64),
            "ex_k1": np.zeros(total, np.uint64),
            "ex_k2": np.zeros(total, np.uint64),
        }
        src = {"cell_id": cell_id, "k1": k1, "k2": k2, "ex_k1": ex_k1, "ex_k2": ex_k2}
        # positions[i] = where original row i lives in the flat layout
        positions = np.empty(n, np.int64)
        start = 0
        for d in range(n_dev):
            rows = order[start : start + loads[d]]
            dst = np.arange(d * chunk, d * chunk + loads[d])
            positions[rows] = dst
            for name, a in src.items():
                cols[name][dst] = a[rows]
            start += loads[d]

        shd = sharding(mesh)
        args = [put_sharded(cols[k], shd) for k in
                ("cell_id", "k1", "k2", "ex_k1", "ex_k2")]
        # ONE transfer wave for all 8 outputs (ops.to_host_many).
        xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid, digest = (
            to_host_many(*_compiled_kernel(mesh)(*args))
        )

        xor_flat, upsert_flat = unpermute_masks(xor_s, upsert_s, i_s, block_size=chunk)
        xor_mask = xor_flat[positions]
        upsert_mask = upsert_flat[positions]

        # XOR-combine per-minute deltas across shards (exact: XOR
        # monoid; the shared decoder merges repeated minute keys).
        by_owner = decode_owner_minute_deltas(
            np.zeros_like(minute_sorted), minute_sorted, seg_end, seg_xor, valid
        )
        deltas: Dict[str, int] = by_owner.get(0, {})
        return xor_mask, upsert_mask, deltas, int(digest)
