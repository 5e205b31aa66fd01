"""Sharded multi-owner reconcile — the pod-scale merge pass.

Replaces the relay's per-user, per-message loop (reference
apps/server/src/index.ts:148-159) and the client's per-message
applyMessages loop with ONE device dispatch for a whole fleet of
owners: owners are assigned to mesh shards (never split), each device
plans its owners' LWW merges and per-(owner, minute) Merkle XOR
deltas locally, and the only cross-device traffic is the final XOR
all-reduce of the batch digest. XOR is associative and commutative,
so combining per-shard digests over ICI is exact (SURVEY.md §2.15).

Cell ids are interned per owner then offset by a global base, so a
flat shard holds many owners yet `plan_merge_core`'s cell segmentation
keeps them apart. The (owner, minute) segment key is an int32 pair
(owner in the hi key, JS-wrapped minute in the lo key; masked rows
park under the int32-max hi sentinel) so the segmented sort stays
fully 32-bit.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.obs import metrics
from evolu_tpu.ops import bucket_size, to_host_many, with_x64
from evolu_tpu.ops.encode import timestamp_hashes, unpack_ts_keys
from evolu_tpu.ops.merge import (
    _PAD_CELL,
    masks_from_sorted_flags,
    messages_to_columns,
    plan_merge_sorted_flags,
    select_messages,
    unpermute_masks,
    winner_flags,
)
from evolu_tpu.ops.merkle_ops import decode_owner_minute_deltas, owner_minute_segments
from evolu_tpu.parallel.mesh import (
    OWNERS_AXIS,
    assign_owners_to_shards,
    put_sharded,
    require_single_process,
    sharding,
)
from evolu_tpu.utils.log import log, span



def xor_allreduce(x, axis_name: str = OWNERS_AXIS):
    """XOR-combine a per-shard value across the mesh axis.

    XLA has no XOR collective; all_gather + local XOR-reduce is one
    ICI round and exact for the associative/commutative XOR monoid.
    """
    gathered = jax.lax.all_gather(x, axis_name)
    return jax.lax.reduce(gathered, jnp.uint32(0), jnp.bitwise_xor, (0,))


# Packed-owner sort key layout (r5): owner(12) | cell(25) | idx(24) |
# flags(2) = 63 bits — the whole per-row identity rides the ONE i64
# sort key, so the merge sort carries only the two u64 HLC keys as
# payloads (the owner i32 payload measured ~0.28 ms/1M on v5e).
# Owner value 4095 is the padding sentinel (sorts last), so real
# owners must be < 4095 and cell ids < 2^25; `shard_kernel_for` routes
# batches exceeding either bound to `_shard_kernel_wide` on HOST data.
_OWNER_BITS, _CELL_BITS = 12, 25
_PAD_OWNER = (1 << _OWNER_BITS) - 1


def pack_owner_cell_key(owner_ix, cell_id, idx, lo_bits: int = 2, lo=None):
    """ONE copy of the packed-owner i64 sort-key layout:
    owner(12) | cell(25) | idx(24) | lo(lo_bits). Shared by the LWW
    shard kernel (lo_bits=2 stored-winner flag bits) and the typed
    CRDT fold kernels (`ops.crdt_merge.counter_shard_sums_core`,
    lo_bits=0 — the sum monoid needs no flags), so the (owner, cell)
    grouping contract can never drift between them. Padding rows
    (cell_id == _PAD_CELL) take the _PAD_OWNER sentinel and sort last.
    Traceable; raises at trace time outside enable_x64(True)."""
    own = jnp.where(
        cell_id == _PAD_CELL, jnp.int64(_PAD_OWNER), owner_ix.astype(jnp.int64)
    )
    key = (
        (own << jnp.int64(_CELL_BITS + 24 + lo_bits))
        | ((cell_id.astype(jnp.int64) & jnp.int64((1 << _CELL_BITS) - 1))
           << jnp.int64(24 + lo_bits))
        | (idx.astype(jnp.int64) << jnp.int64(lo_bits))
    )
    if lo is not None:
        key = key | lo
    if key.dtype != jnp.dtype("int64"):  # x64 disabled: would mis-plan
        raise TypeError(
            "pack_owner_cell_key must be traced under enable_x64(True): "
            f"packed key degraded to {key.dtype}"
        )
    return key


def _shard_kernel(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
    """Per-shard reconcile: LWW plan + (owner, minute) XOR deltas +
    shard digest. All inputs are this shard's local (S,) slices.

    Packed-owner variant (the production and bench default): the sort
    key is owner<<51 | cell<<26 | idx<<2 | eq<<1 | gt (stored-winner
    flag bits as in `plan_merge_sorted_flags`), segments group by
    (owner, cell) — identical segmentation to cell-grouping because
    cell ids are unique per owner (global interning; every caller's
    layout guarantees it). The sorted HLC keys give back the timestamp
    columns, hashing and the (owner, minute) segmented XOR consume the
    sorted rows directly, and the two bool masks return to the host
    with `i_s` for a vectorized numpy unpermute — no device restoring
    sort. Must be traced under enable_x64(True)."""
    n = cell_id.shape[0]
    if n > 1 << 24:  # idx no longer fits its 24 key bits
        return _shard_kernel_wide(cell_id, k1, k2, ex_k1, ex_k2, owner_ix)
    idx = jnp.arange(n, dtype=jnp.int32)
    a, b = winner_flags(k1, k2, ex_k1, ex_k2)
    key = pack_owner_cell_key(
        owner_ix, cell_id, idx, lo_bits=2,
        lo=(b.astype(jnp.int64) << jnp.int64(1)) | a.astype(jnp.int64),
    )
    key_s, s1, s2 = jax.lax.sort((key, k1, k2), num_keys=1, is_stable=False)
    owner_s = (key_s >> jnp.int64(_CELL_BITS + 26)).astype(jnp.int32)
    i_s = ((key_s >> jnp.int64(2)) & jnp.int64((1 << 24) - 1)).astype(jnp.int32)
    a_s = (key_s & jnp.int64(1)) != 0
    b_s = (key_s & jnp.int64(2)) != 0
    real = owner_s != jnp.int32(_PAD_OWNER)
    # Segment key = key bits above idx/flags = (owner, cell); the mask
    # algebra is the ONE shared copy in ops.merge.
    xor_s, upsert_s = masks_from_sorted_flags(
        key_s >> jnp.int64(26), s1, s2, a_s, b_s, real
    )

    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(
        xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0)
    )
    owner_sorted, minute_sorted, seg_end_m, seg_xor, valid_sorted = owner_minute_segments(
        owner_s, millis_s, hashes, xor_s
    )
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return (
        xor_s, upsert_s, i_s,
        owner_sorted, minute_sorted, seg_end_m, seg_xor, valid_sorted, digest,
    )


def _shard_kernel_wide(cell_id, k1, k2, ex_k1, ex_k2, owner_ix):
    """The wide-id fallback (cell ≥ 2^25 or owner ≥ 4095): owner rides
    as an i32 sort payload and segmentation is by cell alone —
    bit-identical masks/deltas/digest whenever the packed variant's
    preconditions hold (parity-pinned), and the only variant that can
    serve batches beyond them."""
    xor_s, upsert_s, i_s, s1, s2, (owner_s,) = plan_merge_sorted_flags(
        cell_id, k1, k2, ex_k1, ex_k2, extras=(owner_ix.astype(jnp.int32),)
    )
    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(
        xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0)
    )
    owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        owner_s, millis_s, hashes, xor_s
    )
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return (
        xor_s, upsert_s, i_s,
        owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted, digest,
    )


def _shard_kernel_scatter(cell_id, k1, k2, ex_k1, ex_k2, owner_ix, table_size):
    """Sort-free per-shard reconcile (ops/scatter_merge.py): the LWW
    masks come from the dense scatter-argmax plan in ORIGINAL shard
    order (i_s is the identity), and the (owner, minute) segmentation
    consumes the original-order columns — its own tile-local grouping
    sort is order-free (decoders XOR-merge per key), so host-level
    plans, deltas, and the digest are bit-identical to the sort
    kernels wherever the router admits a batch. Segmentation-by-cell
    assumption matches `_shard_kernel_wide`'s: cell ids are globally
    interned (unique per owner). Same 9-output contract as
    `_shard_kernel`; must be traced under enable_x64(True)."""
    from evolu_tpu.ops.scatter_merge import scatter_plan_masks

    xor_m, upsert_m = scatter_plan_masks(cell_id, k1, k2, ex_k1, ex_k2, table_size)
    i_s = jnp.arange(cell_id.shape[0], dtype=jnp.int32)
    millis, counter = unpack_ts_keys(k1)
    hashes = jnp.where(xor_m, timestamp_hashes(millis, counter, k2), jnp.uint32(0))
    owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        owner_ix, millis, hashes, xor_m
    )
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return (
        xor_m, upsert_m, i_s,
        owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted, digest,
    )


@functools.lru_cache(maxsize=None)
def scatter_shard_kernel(table_size: int):
    """The scatter shard kernel bound to one static table bucket.
    Cached so repeated batches in the same bucket hand `_compiled_kernel`
    the SAME callable (its lru_cache keys on identity — a fresh partial
    per batch would recompile the mesh kernel every call)."""
    kernel = functools.partial(_shard_kernel_scatter, table_size=table_size)
    kernel.__name__ = f"_shard_kernel_scatter_{table_size}"
    return kernel


def shard_kernel_for(cols: Dict[str, np.ndarray]):
    """Static host-side routing between the scatter plan (when
    configured and admissible — ops/scatter_merge.py), the packed-owner
    sort kernel, and the wide fallback: the packed key needs every real
    cell id < 2^25 and every owner index < 4095 (the padding sentinel);
    the scatter plan needs cell ids < 2^25 and a duplicate-free batch.
    `cols` are the HOST numpy columns, so the choice is made before
    tracing — no device cond, separately compiled kernels."""
    from evolu_tpu.ops.scatter_merge import table_size_for, use_scatter_plan

    real = cols["cell_id"] != int(_PAD_CELL)
    cell_max = int(cols["cell_id"].max(initial=0, where=real))
    owner_max = int(cols["owner_ix"].max(initial=0))
    if "k1" in cols and use_scatter_plan(
        cols["cell_id"], cols["k1"], cols["k2"], cell_max=cell_max
    ):
        metrics.inc("evolu_reconcile_kernel_total", variant="scatter")
        return scatter_shard_kernel(table_size_for(cell_max))
    if cell_max < (1 << _CELL_BITS) and owner_max < _PAD_OWNER:
        metrics.inc("evolu_reconcile_kernel_total", variant="packed")
        return _shard_kernel
    metrics.inc("evolu_reconcile_kernel_total", variant="wide")
    return _shard_kernel_wide


@functools.lru_cache(maxsize=None)
def _compiled_kernel(mesh: Mesh, kernel=None):
    spec = P(OWNERS_AXIS)
    mapped = shard_map(
        kernel or _shard_kernel,
        mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=(spec,) * 8 + (P(),),
        check_vma=False,
    )
    return jax.jit(mapped)


@with_x64
def reconcile_columns_sharded(mesh: Mesh, cols: Dict[str, np.ndarray]):
    """Run the sharded kernel on flat global columns (length D*S, owner
    blocks laid out shard-contiguously). Returns device arrays:
    (xor_sorted, upsert_sorted, i_s, owner_sorted, minute_sorted,
    seg_end, seg_xor, seg_valid, digest) — masks are in per-shard
    cell-sorted order; `unpermute_masks(..., block_size=shard_size)`
    restores batch order on the host. Works on a multi-process
    cluster: every process builds the same global columns, feeds its
    local shards (`put_sharded`), and pulls back only its addressable
    outputs (`to_host` concatenates addressable shards) — the digest
    is replicated by the XOR all-reduce, so every process sees the
    whole-batch digest while owning only its shards' plans."""
    shd = sharding(mesh)
    args = [
        put_sharded(cols[k], shd)
        for k in ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")
    ]
    return _compiled_kernel(mesh, shard_kernel_for(cols))(*args)


def build_owner_columns(
    mesh: Mesh,
    owner_batches: Dict[str, Sequence[CrdtMessage]],
    existing_winners: Dict[str, Dict[Tuple[str, str, str], str]],
    mesh_ctx=None,
):
    """Host-side layout: per-owner columnarization → shard assignment →
    flat padded global columns + bookkeeping to scatter results back.

    Returns (cols, index, host_owners): `host_owners` are owners whose
    batch (or stored winners) contain non-canonical hex case — the
    device's numeric order / canonical-render hash would diverge from
    the reference's raw-string semantics for them, so they are excluded
    from the layout and must be planned on the host. Owners are
    independent, so this quarantine is per owner, not per batch.
    """
    n_shards = mesh.devices.size
    owners = []
    host_owners = []
    per_owner = {}
    cell_base = 0
    for o in owner_batches:
        msgs = owner_batches[o]
        cols = messages_to_columns(msgs, existing_winners.get(o, {}))
        cell_ids, k1, k2, ex_k1, ex_k2, millis, counter, node, canonical = cols
        if not canonical:
            host_owners.append(o)
            continue
        owners.append(o)
        cell_ids = cell_ids + cell_base
        cell_base += len(msgs)  # intern ids are < len(msgs)
        per_owner[o] = (cell_ids, k1, k2, ex_k1, ex_k2, millis, counter, node)
    owner_ix = {o: i for i, o in enumerate(owners)}

    sizes = {o: len(owner_batches[o]) for o in owners}
    if mesh_ctx is not None:
        # PR-12 sharded path: STABLE owner→device placement (an owner
        # lands on the same device every batch — the precondition for
        # device-resident per-owner state such as the mesh-sharded
        # winner cache), occupancy/padding telemetry recorded.
        shards = mesh_ctx.assign_stable(sizes)
    else:
        shards = assign_owners_to_shards(sizes, n_shards)
    # Shard balance telemetry: the assignment's per-shard row loads
    # (host ints already in hand — arXiv:2004.00107's point that
    # anti-entropy behavior is only debuggable with per-round telemetry
    # applies doubly to a load imbalance that serializes the mesh).
    loads = [sum(len(owner_batches[o]) for o in s) for s in shards]
    for load in loads:
        metrics.observe("evolu_reconcile_shard_rows", load,
                        buckets=metrics.COUNT_BUCKETS)
    shard_size = bucket_size(max(max(loads, default=0), 1))
    if mesh_ctx is not None:
        mesh_ctx.record_occupancy(loads, shard_size)
        mesh_ctx.record_xdev_reduce("digest")

    # Timestamp columns are NOT laid out: the kernels recover
    # millis/counter/node from the sorted HLC keys, so transferring
    # them would be dead H2D traffic.
    total = n_shards * shard_size
    out = {
        "cell_id": np.full(total, int(_PAD_CELL), np.int32),
        "k1": np.zeros(total, np.uint64),
        "k2": np.zeros(total, np.uint64),
        "ex_k1": np.zeros(total, np.uint64),
        "ex_k2": np.zeros(total, np.uint64),
        "owner_ix": np.zeros(total, np.int64),
    }
    index: Dict[str, Tuple[np.ndarray, int]] = {}
    for si, shard in enumerate(shards):
        pos = si * shard_size
        for o in shard:
            cell_ids, k1, k2, ex_k1, ex_k2, _millis, _counter, _node = per_owner[o]
            n = len(cell_ids)
            sl = slice(pos, pos + n)
            out["cell_id"][sl] = cell_ids
            out["k1"][sl], out["k2"][sl] = k1, k2
            out["ex_k1"][sl], out["ex_k2"][sl] = ex_k1, ex_k2
            out["owner_ix"][sl] = owner_ix[o]
            index[o] = (np.arange(pos, pos + n), owner_ix[o])
            pos += n
    return out, index, host_owners


def reconcile_owner_batches(
    mesh: Mesh,
    owner_batches: Dict[str, Sequence[CrdtMessage]],
    existing_winners: Dict[str, Dict[Tuple[str, str, str], str]],
    mesh_ctx=None,
):
    """Full multi-owner reconcile: one device dispatch for all owners.

    Returns ({owner: (xor_mask, upserts, minute_deltas)}, digest) with
    the same per-owner contract as the single-owner planner
    (`storage.apply.plan_batch` + the host Merkle delta pass), so the
    caller can apply results to per-owner SQLite stores / trees.
    """
    if not owner_batches:
        return {}, 0
    require_single_process("reconcile_owner_batches")
    n_msgs = sum(len(v) for v in owner_batches.values())
    metrics.observe("evolu_reconcile_batch_rows", n_msgs,
                    buckets=metrics.COUNT_BUCKETS)
    metrics.observe("evolu_reconcile_batch_owners", len(owner_batches),
                    buckets=metrics.COUNT_BUCKETS)
    with span("kernel:reconcile", "reconcile_owner_batches",
              owners=len(owner_batches), n=n_msgs):
        return _reconcile_owner_batches_timed(
            mesh, owner_batches, existing_winners, mesh_ctx
        )


def _reconcile_owner_batches_timed(mesh, owner_batches, existing_winners,
                                   mesh_ctx=None):
    cols, index, host_owners = build_owner_columns(
        mesh, owner_batches, existing_winners, mesh_ctx=mesh_ctx
    )
    results = {}
    digest = 0
    if index:
        # ONE transfer wave for all 9 kernel outputs instead of nine
        # blocking pulls (see ops.to_host_many).
        xor_s, upsert_s, i_s, owner_sorted, minute_sorted, seg_end, seg_xor, seg_valid, dev_digest = (
            to_host_many(*reconcile_columns_sharded(mesh, cols))
        )
        shard_size = len(cols["cell_id"]) // mesh.devices.size
        xor_mask, upsert_mask = unpermute_masks(xor_s, upsert_s, i_s, block_size=shard_size)
        deltas_by_ix = decode_owner_minute_deltas(
            owner_sorted, minute_sorted, seg_end, seg_xor, seg_valid
        )
        digest = int(dev_digest)
        for owner, (positions, o_ix) in index.items():
            messages = owner_batches[owner]
            o_mask = upsert_mask[positions]
            results[owner] = (
                xor_mask[positions].tolist(),
                select_messages(messages, o_mask),
                deltas_by_ix.get(o_ix, {}),
            )
    metrics.inc("evolu_reconcile_host_owner_fallbacks_total", len(host_owners))
    for owner in host_owners:
        log("kernel:reconcile", "non-canonical hex case: host-planner fallback",
            owner=owner, n=len(owner_batches[owner]))
        plan, owner_digest = _host_owner_plan(
            owner_batches[owner], existing_winners.get(owner, {})
        )
        results[owner] = plan
        digest ^= owner_digest
    return results, digest


def _host_owner_plan(messages, winners):
    """Oracle-exact host plan for one quarantined owner: raw-string LWW
    order + the shared verbatim-case hash fold."""
    from evolu_tpu.core.merkle import minute_deltas_host
    from evolu_tpu.storage.apply import plan_batch

    xor_mask, upserts = plan_batch(messages, winners)
    deltas, digest = minute_deltas_host(
        m.timestamp for flag, m in zip(xor_mask, messages) if flag
    )
    return (xor_mask, upserts, deltas), digest
