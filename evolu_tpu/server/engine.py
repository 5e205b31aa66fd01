"""Batch reconcile engine — many owners' sync rounds in one device pass.

The reference relay handles one user per HTTP request, inserting and
hashing message-by-message (apps/server/src/index.ts:148-159). This
engine takes a whole batch of SyncRequests (config 3: 1M messages
across 1k owners), and:

1. set-diffs incoming timestamps against storage in bulk SQL (the
   INSERT OR IGNORE dedup, batched via a temp-table join);
2. hashes every new timestamp and reduces per-(owner, minute) XOR
   deltas on device (`owner_minute_segments` over int32 owner/minute
   key pairs, sharded over the mesh; an owner bigger than an even
   shard's worth of rows row-splits across shards — safe because the
   decoder XOR-merges repeated (owner, minute) partials exactly);
3. applies the deltas to each owner's sparse tree, persists, and
   answers each request with the standard diff response.

The relay is E2EE-blind, so this touches only timestamps and
ciphertext blobs — the LWW cell merge happens client-side.
"""

from __future__ import annotations

import contextvars
import functools
import threading
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    merkle_tree_from_string,
    merkle_tree_to_string,
)
from evolu_tpu.ops import bucket_size, to_host_many, with_x64
from evolu_tpu.ops.encode import timestamp_hashes
from evolu_tpu.ops.host_parse import (
    pack_requests,
    parse_packed_timestamps,
    parse_timestamp_strings,
)
from evolu_tpu.ops.merkle_ops import decode_owner_minute_deltas, owner_minute_segments
from evolu_tpu.parallel.mesh import (
    OWNERS_AXIS,
    assign_owners_to_shards,
    create_mesh,
    put_sharded,
    require_single_process,
    sharding,
)
from evolu_tpu.obs import anatomy, flight, ledger, metrics
from evolu_tpu.parallel.reconcile import xor_allreduce
from evolu_tpu.server.store import (
    ShardedRelayStore, fetch_response_stream, response_since,
)
from evolu_tpu.storage.native import relay_commit_shards, relay_insert_packed_shards
from evolu_tpu.utils.log import log, span
from evolu_tpu.sync import protocol

# Every compiled Merkle kernel, for the recompile fence: the scheduler
# pins `merkle_jit_cache_size()` flat across varying micro-batch sizes
# (bucket-stable shapes mean jit compiles per BUCKET, never per batch).
_JIT_KERNELS: List = []


def merkle_jit_cache_size() -> int:
    """Total jit-cache entries across the engine's compiled kernels.
    `_cache_size` is a private jax surface (present in the installed
    jax 0.9.0; the same one bench.py's liveness fence uses)."""
    return sum(k._cache_size() for k in _JIT_KERNELS)


# Recompile sentinel (ISSUE 15 satellite): last-observed cache sizes,
# diffed after each scheduler batch. Single-writer by construction —
# only the scheduler's dispatcher thread calls observe_jit_caches.
_JIT_SENTINEL_SIZES: Dict[str, int] = {}


def observe_jit_caches(batch_rows: int = 0) -> Dict[str, int]:
    """The CLAUDE.md recompile fence, observable in production: export
    `evolu_jit_cache_size{cache}` gauges and grow
    `evolu_jit_recompiles_total{cache}` by the diff of
    `merkle_jit_cache_size()` / `mesh_jit_cache_size()` since the last
    batch. Growth also drops a flight-recorder event naming the batch
    bucket shape that triggered it — the post-mortem answer to "which
    shape broke bucket stability". The FIRST observation is the
    baseline (warm-up compiles between observation 1 and 2 count;
    steady-state traffic within a bucket must then stay flat —
    test-pinned). Returns {cache: size}."""
    from evolu_tpu.ops.winner_cache import mesh_jit_cache_size

    sizes = {"merkle": merkle_jit_cache_size(),
             "mesh": mesh_jit_cache_size()}
    for cache, size in sizes.items():
        metrics.set_gauge("evolu_jit_cache_size", size, cache=cache)
        prev = _JIT_SENTINEL_SIZES.get(cache)
        if prev is not None and size > prev:
            metrics.inc("evolu_jit_recompiles_total", size - prev,
                        cache=cache)
            flight.record(
                "kernel:jit", "jit cache grew", cache=cache,
                new_entries=size - prev, total_entries=size,
                batch_rows=batch_rows,
                bucket_rows=bucket_size(max(1, batch_rows)),
            )
        _JIT_SENTINEL_SIZES[cache] = size
    return sizes


def _merkle_shard_kernel(millis, counter, node, valid, owner_ix):
    """Per-shard (owner, minute) XOR deltas + allreduced batch digest
    (`owner_minute_segments` is shared with the client reconcile
    kernel, parallel/reconcile.py)."""
    hashes = jnp.where(valid, timestamp_hashes(millis, counter, node), jnp.uint32(0))
    out = owner_minute_segments(owner_ix, millis, hashes, valid)
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return (*out, digest)


@functools.lru_cache(maxsize=None)
def _compiled_merkle_kernel(mesh: Mesh):
    spec = P(OWNERS_AXIS)
    fn = jax.jit(
        shard_map(
            _merkle_shard_kernel,
            mesh=mesh,
            in_specs=(spec,) * 5,
            out_specs=(spec, spec, spec, spec, spec, P()),
            check_vma=False,
        )
    )
    _JIT_KERNELS.append(fn)
    return fn


def _compact_segments_tail(owner_ix, millis, counter, node, valid, cap):
    """ONE copy of the correctness-sensitive compaction tail shared by
    both compact kernels (the full-key and delta-encoded uploads must
    stay output-identical): hash → per-(owner, minute) segments with
    tile_local=False (the compaction cap is budgeted against DISTINCT
    keys; tile partials would multiply seg_count by up to
    shard_size/8192 and flip realistic workloads into the full-pull
    fallback — r4 review finding) → stable float-real-entries-to-front
    sort (so the host pulls `cap` segment entries, not N rows) →
    (packed owner<<32|minute keys[cap], xors[cap],
    seg_count, digest); seg_count > cap signals overflow (caller falls
    back to the full pull)."""
    hashes = jnp.where(valid, timestamp_hashes(millis, counter, node), jnp.uint32(0))
    owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        owner_ix, millis, hashes, valid, tile_local=False
    )
    is_seg = seg_end & valid_sorted
    packed = (owner_sorted.astype(jnp.uint64) << jnp.uint64(32)) | minute_sorted.astype(
        jnp.uint32
    ).astype(jnp.uint64)
    _, packed_s, xor_s = jax.lax.sort(
        (~is_seg, packed, seg_xor), num_keys=1, is_stable=True
    )
    seg_count = jnp.sum(is_seg.astype(jnp.int32)).reshape(1)
    digest = xor_allreduce(jax.lax.reduce(hashes, jnp.uint32(0), jnp.bitwise_xor, (0,)))
    return packed_s[:cap], xor_s[:cap], seg_count, digest


def _merkle_shard_kernel_compact(k1, node, owner_ix, cap):
    """Transfer-lean variant: 20 bytes/row up (packed HLC key, node,
    int32 owner with -1 marking padding), segments compacted on device
    to `cap` entries via the shared tail above."""
    from evolu_tpu.ops.encode import unpack_ts_keys

    valid = owner_ix >= 0
    millis, counter = unpack_ts_keys(k1)
    return _compact_segments_tail(owner_ix, millis, counter, node, valid, cap)


# Owner field bits in the delta-compact upload's owner|counter column.
# Owner 0xFFFF is the padding sentinel, so ≤ 65534 distinct owners per
# dispatch ride the 16-byte/row path; bigger batches (or millis spans
# ≥ 2^32 ms ≈ 49.7 days, or pre-1970 rows) keep the 20-byte kernel.
_DELTA_OWNER_BITS = 16
_DELTA_PAD_OWNER = (1 << _DELTA_OWNER_BITS) - 1


def _merkle_shard_kernel_compact_delta(dmillis, ownctr, node, base, cap):
    """The compact kernel with the key column DELTA-ENCODED against the
    batch minimum (VERDICT #9): uploads are 16 bytes/row — u32
    millis-delta, u32 owner<<16|counter (owner 0xFFFF = padding), u64
    node — instead of 20 (u64 packed HLC key + i32 owner): 4 bytes/row
    less host→device upload (what a byte costs on the attached chip:
    not measured). `base` is the batch-minimum millis, replicated to every shard as a
    (1,) int64; millis reconstruct exactly (host routing guarantees
    every delta fits u32). Outputs identical to
    `_merkle_shard_kernel_compact` — the whole segment/cap/digest tail
    is the ONE shared `_compact_segments_tail`."""
    owner16 = ownctr >> jnp.uint32(16)
    valid = owner16 != jnp.uint32(_DELTA_PAD_OWNER)
    owner_ix = jnp.where(valid, owner16.astype(jnp.int32), jnp.int32(-1))
    millis = base[0] + dmillis.astype(jnp.int64)
    counter = (ownctr & jnp.uint32(0xFFFF)).astype(jnp.int32)
    return _compact_segments_tail(owner_ix, millis, counter, node, valid, cap)


def _pack_outputs(packed, xors, seg_count, digest):
    """One u64 array a device for the four outputs of the compact tail:
    `packed` [cap], `xors` two a word (the first half in the low
    halves, the second in the high: contiguous slices on both sides),
    then seg_count | digest << 32. `_unpack_outputs` is its host
    inverse."""
    half = xors.shape[0] // 2
    lo, hi = xors[:half].astype(jnp.uint64), xors[half:].astype(jnp.uint64)
    last = seg_count.astype(jnp.uint64) | (digest.astype(jnp.uint64) << jnp.uint64(32))
    return jnp.concatenate([packed, lo | (hi << jnp.uint64(32)), last.reshape(1)])


def _halves(words):
    """The low and the high u32 half of every u64 word."""
    return words.astype(jnp.uint32), (words >> jnp.uint64(32)).astype(jnp.uint32)


def _packed_kernel_delta(buf, cap):
    """`_merkle_shard_kernel_compact_delta` behind ONE upload a device:
    2S + 1 words, dmillis | ownctr << 32 a row, then `node`, then
    `base` (in every device's tail, so it needs no replicated twin)."""
    s = (buf.shape[0] - 1) // 2
    dmillis, ownctr = _halves(buf[:s])
    return _pack_outputs(*_merkle_shard_kernel_compact_delta(
        dmillis, ownctr, buf[s:2 * s], buf[2 * s:].astype(jnp.int64), cap))


def _packed_kernel_full(buf, cap):
    """`_merkle_shard_kernel_compact` behind one upload a device: 2.5 S
    words, `k1`, `node`, then `oix` two a word (first half of the rows
    in the low halves)."""
    s = buf.shape[0] * 2 // 5
    oix = jax.lax.bitcast_convert_type(
        jnp.concatenate(_halves(buf[2 * s:])), jnp.int32)
    return _pack_outputs(*_merkle_shard_kernel_compact(buf[:s], buf[s:2 * s], oix, cap))


@functools.lru_cache(maxsize=None)
def _compiled_packed_kernel(mesh: Mesh, cap: int, delta: bool):
    """The program of one relay pass: one host buffer in (a NUMPY array
    handed to the call, whose C++ path uploads it: no Python
    `device_put`), one device array out."""
    shd = sharding(mesh)
    fn = jax.jit(
        shard_map(
            functools.partial(_packed_kernel_delta if delta else _packed_kernel_full,
                              cap=cap),
            mesh=mesh,
            in_specs=P(OWNERS_AXIS),
            out_specs=P(OWNERS_AXIS),
            check_vma=False,
        ),
        in_shardings=shd,
        out_shardings=shd,
    )
    _JIT_KERNELS.append(fn)
    return fn


def _unpack_outputs(out, n_devices: int, cap: int):
    """→ (packed [n, cap], xors [n, cap], counts [n], digest) of the
    pulled `_pack_outputs` rows."""
    rows = out.reshape(n_devices, -1)
    x32 = rows[:, cap:cap + cap // 2].view(np.uint32)
    xors = np.concatenate((x32[:, 0::2], x32[:, 1::2]), axis=1)
    last = rows[:, -1]
    return (rows[:, :cap], xors, (last & np.uint64(0xFFFFFFFF)).astype(np.int64),
            int(last[0] >> np.uint64(32)))


@with_x64
def owner_minute_deltas(
    mesh: Mesh, owner_rows: Dict[str, Sequence[str]], ctx=None
) -> Tuple[Dict[str, Dict[str, int]], int]:
    """Device pass: {owner: [timestamp strings]} → per-owner
    {minute-key: xor delta} plus the global batch digest.

    The device hash re-renders the node hex lowercase; the reference
    hashes the parsed node verbatim (timestampToHash of the parsed
    Timestamp, index.ts:155). Owners whose rows carry non-canonical hex
    case are quarantined to the shared host fold (the per-row case flag
    rides out of the batch parse, costing nothing extra); the other
    owners in the batch stay on device — owners are independent."""
    with span("kernel:merkle", "owner_minute_deltas",
              owners=len(owner_rows),
              n=sum(len(v) for v in owner_rows.values())):
        return _owner_minute_deltas_timed(mesh, owner_rows, ctx)


def _owner_minute_deltas_timed(mesh, owner_rows, ctx=None):
    owners = list(owner_rows)
    # ONE vectorized parse for every owner's timestamps (per-owner calls
    # would pay the numpy setup ~owners times); the per-row case flags
    # mark owners that must take the host fold.
    flat = [ts for o in owners for ts in owner_rows[o]]
    all_m, all_c, all_n, case_ok = parse_timestamp_strings(flat, with_case=True)
    owner_index: Dict[str, np.ndarray] = {}
    pos = 0
    for o in owners:
        k = len(owner_rows[o])
        owner_index[o] = np.arange(pos, pos + k)
        pos += k
    return deltas_from_columns(
        mesh, owner_index, all_m, all_c, all_n, case_ok, flat, ctx=ctx
    )


@with_x64
def deltas_from_columns(
    mesh: Mesh,
    owner_index: Dict[str, np.ndarray],
    all_m: np.ndarray,
    all_c: np.ndarray,
    all_n: np.ndarray,
    case_ok: np.ndarray,
    ts_strings: Sequence[str],
    ctx=None,
) -> Tuple[Dict[str, Dict[str, int]], int]:
    """Device Merkle pass over already-parsed columns: `owner_index`
    maps owner → row indices to hash (callers pre-filter to the rows
    that were actually inserted). Owners touching any non-canonical row
    are quarantined to the shared host fold (`ts_strings` provides the
    raw strings for it); everyone else rides one sharded dispatch."""
    return deltas_finish(
        deltas_dispatch(
            mesh, owner_index, all_m, all_c, all_n, case_ok, ts_strings, ctx=ctx
        )
    )


@with_x64
def deltas_dispatch(
    mesh: Mesh,
    owner_index: Dict[str, np.ndarray],
    all_m: np.ndarray,
    all_c: np.ndarray,
    all_n: np.ndarray,
    case_ok: np.ndarray,
    ts_strings: Sequence[str],
    ctx=None,
    pull_pool=None,
    tile=None,
):
    """First half of `deltas_from_columns` — host packing, device
    dispatch, async transfer START. Returns an opaque state for
    `deltas_finish`. Between the two calls the device computes and the
    outputs copy back, so a pipelining caller can run batch k's SQLite
    work while batch k+1 is in flight here. With a `pull_pool` (a
    one-thread executor) the blocking pull starts there NOW: its wait
    releases the GIL, so the transfer overlaps the caller's host leg.

    Two stage records tile the call (obs.anatomy): `pass_layout`, the
    host's numpy work up to the last array built, and
    `pass_device_call`, what ONE device call costs the host on this
    chip — uploads, the jit call, the async transfer start and the
    hand-off to the pull thread. A caller that tiles a parent stage
    passes its running `tile`: the two continue it seam to seam and it
    comes back running as `pass_device_call`, the caller's to stop.

    With a `ctx` (parallel.mesh.MeshContext — the PR-12 sharded-engine
    path), the layout uses STABLE owner→device placement
    (`ctx.assign_stable`) instead of per-batch LPT, and records the
    per-device occupancy / padding-waste / cross-device-reduce
    telemetry. The kernels, decode, and outputs are IDENTICAL — only
    row layout changes, and the delta decoders are layout-agnostic, so
    the sharded path is byte-identical by construction (parity-pinned
    in tests/test_mesh_engine.py anyway)."""
    require_single_process("engine.deltas_from_columns")
    if tile is None:
        tile = anatomy.stage("pass_layout")
        try:
            return deltas_dispatch(mesh, owner_index, all_m, all_c, all_n,
                                   case_ok, ts_strings, ctx, pull_pool, tile)
        finally:
            tile.stop()
    tile.then("pass_layout")
    deltas, digest, good, layout = _deltas_layout(
        mesh, owner_index, all_m, all_c, all_n, case_ok, ts_strings, ctx
    )
    if layout is None:
        return (deltas, digest, good, None, None)
    buf, k1, oix, cap, delta, tile.rows = layout
    tile.then("pass_device_call", rows=tile.rows)
    out = _compiled_packed_kernel(mesh, cap, delta)(buf)
    out.copy_to_host_async()
    metrics.inc_many((
        ("evolu_engine_compact_upload_bytes_total", buf.nbytes,
         {"variant": "delta" if delta else "full"}),
        ("evolu_engine_device_dispatches_total", 1, {}),
        ("evolu_engine_device_transfers_total", 1, {"dir": "up"}),
        ("evolu_engine_device_transfers_total", 1, {"dir": "down"}),
    ))
    outs = (out,)
    if pull_pool is not None:
        outs = pull_pool.submit(to_host_many, out)
    return (deltas, digest, good, outs, (buf, k1, oix, mesh, cap))


def _deltas_layout(mesh, owner_index, all_m, all_c, all_n, case_ok,
                   ts_strings, ctx):
    """The host half of `deltas_dispatch`: case check (non-canonical
    owners fold on the host here), owner units, shard assignment,
    bucket padding, key packing. → (deltas, digest, good, layout);
    `layout` is None where no owner is left for the device, else
    (buf, k1, oix, cap, delta, rows): `buf` the pass's ONE upload
    (`_packed_kernel_delta`'s words where `delta`, 16 B a row, else
    `_packed_kernel_full`'s, 20 B), `k1` and `oix` the host's copies
    for the overflow re-run, `rows` the real (unpadded) row count."""
    owners = list(owner_index)
    deltas: Dict[str, Dict[str, int]] = {o: {} for o in owners}
    digest = 0
    host_owners = [
        o for o, ix in owner_index.items() if len(ix) and not case_ok[ix].all()
    ]
    if host_owners:
        log("kernel:merkle", "non-canonical hex case: host hashing fallback",
            owners=len(host_owners))
        from evolu_tpu.core.merkle import minute_deltas_host

        for o in host_owners:
            deltas[o], d = minute_deltas_host(ts_strings[i] for i in owner_index[o])
            digest ^= d

    quarantined = set(host_owners)
    sizes = {o: len(owner_index[o]) for o in owners}
    good = [o for o in owners if o not in quarantined and sizes[o]]
    if not good:
        return deltas, digest, good, None

    owner_ix = {o: i for i, o in enumerate(good)}
    # Hot-owner split: hashing needs no cell locality, and the decoder
    # XOR-merges repeated (owner, minute) keys exactly, so an owner
    # bigger than an even shard's worth of rows splits row-wise across
    # shards instead of capping one shard's load (SURVEY.md §5).
    n_good_rows = sum(sizes[o] for o in good)
    target = max(1, -(-n_good_rows // mesh.devices.size))  # ceil
    units: Dict[Tuple[str, int], np.ndarray] = {}
    for o in good:
        ix = owner_index[o]
        if len(ix) <= target:
            units[(o, 0)] = ix
        else:
            for j, start in enumerate(range(0, len(ix), target)):
                units[(o, j)] = ix[start : start + target]
    unit_sizes = {u: len(ix) for u, ix in units.items()}
    if ctx is not None:
        shards = ctx.assign_stable(unit_sizes)
    else:
        shards = assign_owners_to_shards(unit_sizes, mesh.devices.size)
    loads = [sum(len(units[u]) for u in s) for s in shards]
    shard_size = bucket_size(max(max(loads, default=0), 1))
    if ctx is not None:
        ctx.record_occupancy(loads, shard_size)
        # The in-kernel XOR all-reduce of the batch digest is one
        # cross-device reduction per dispatch; owners whose row-split
        # chunks landed on several devices additionally XOR-merge
        # their (owner, minute) partials in the host decode.
        ctx.record_xdev_reduce("digest")
        shard_of = {u: si for si, s in enumerate(shards) for u in s}
        split_owners = {}
        for (o, _j), si in shard_of.items():
            split_owners.setdefault(o, set()).add(si)
        for o, devs in split_owners.items():
            if len(devs) > 1:
                ctx.record_xdev_reduce("owner_delta_partials")

    # The columns of the transfer-lean upload, a row a device: packed
    # HLC key (millis<<16 | counter), node, and int32 owner with -1
    # marking padding (the timestamp columns are rebuilt on device from
    # the packed key). `node` is written where it is uploaded from:
    # words [S, 2S) of each device's row of the pass's one buffer.
    n_dev = len(shards)
    buf = np.zeros((n_dev, 2 * shard_size + 1), np.uint64)
    k1 = np.zeros((n_dev, shard_size), np.uint64)
    node = buf[:, shard_size:2 * shard_size]
    oix = np.full((n_dev, shard_size), -1, np.int32)
    pos_by_shard = [0] * n_dev
    shard_of_unit = {u: si for si, shard in enumerate(shards) for u in shard}
    for u, ix in units.items():
        n = len(ix)
        si = shard_of_unit[u]
        pos = pos_by_shard[si]
        sl = slice(pos, pos + n)
        k1[si, sl] = (all_m[ix].astype(np.uint64) << np.uint64(16)) | all_c[ix].astype(
            np.uint64
        )
        node[si, sl] = all_n[ix]
        oix[si, sl] = owner_ix[u[0]]
        pos_by_shard[si] = pos + n
    k1, oix = k1.reshape(-1), oix.reshape(-1)

    cap = bucket_size(max(shard_size // 8, 64))
    real = oix >= 0
    millis = (k1 >> np.uint64(16)).astype(np.int64)
    real_millis = millis[real]
    base = int(real_millis.min()) if len(real_millis) else 0
    # `millis_span`, not `span`: this module's `span` is the timing
    # context manager from utils.log.
    millis_span = (int(real_millis.max()) - base) if len(real_millis) else 0
    # Delta-compact admission (host-side, static): batch span under
    # 2^32 ms, owner indexes under the 16-bit padding sentinel, and no
    # wrapped millis. The k1 packing casts signed millis to u64, so a
    # pre-1970 value surfaces HERE as ~2^48 (never negative — a
    # `base >= 0` guard would be dead code); both kernels treat the
    # wrapped value identically, but wrapped batches keep the full-key
    # kernel so admission stays a statement about true timestamps.
    max_real = base + millis_span
    use_delta = (
        millis_span < (1 << 32)
        and max_real < (1 << 47)  # wrapped pre-1970 lands near 2^48
        and len(good) < _DELTA_PAD_OWNER
    )
    if use_delta:
        dmillis = np.where(real, millis - base, 0).astype(np.uint32)
        ownctr = np.where(
            real,
            (oix.astype(np.uint32) << np.uint32(16))
            | (k1 & np.uint64(0xFFFF)).astype(np.uint32),
            np.uint32(_DELTA_PAD_OWNER << 16),
        )
        halves = buf[:, :shard_size].view(np.uint32)
        halves[:, 0::2] = dmillis.reshape(n_dev, shard_size)
        halves[:, 1::2] = ownctr.reshape(n_dev, shard_size)
        buf[:, -1] = base
    else:
        buf = _full_key_upload(buf, k1, oix)
    return deltas, digest, good, (buf.reshape(-1), k1, oix, cap, use_delta, n_good_rows)


def _full_key_upload(buf, k1, oix):
    """`_packed_kernel_full`'s words from a delta-sized buffer that
    holds `node`: the rare variant pays the copy."""
    n_dev, s = buf.shape[0], buf.shape[1] // 2
    full = np.empty((n_dev, 2 * s + s // 2), np.uint64)
    full[:, :s] = k1.reshape(n_dev, s)
    full[:, s:2 * s] = buf[:, s:2 * s]
    halves, oix = full[:, 2 * s:].view(np.uint32), oix.reshape(n_dev, s)
    halves[:, 0::2], halves[:, 1::2] = oix[:, :s // 2], oix[:, s // 2:]
    return full


def deltas_finish(state) -> Tuple[Dict[str, Dict[str, int]], int]:
    """Second half: wait for the (mostly arrived) compact outputs, then
    decode them. The wait alone is the `pass_pull_wait` stage: the
    device and transfer time the caller's host work did not hide, the
    host-clock reading of "the chip made the host wait". A caller that
    tiles its own stages (`_land`) calls the two halves itself."""
    with anatomy.stage("pass_pull_wait"):
        pulled = deltas_pull(state)
    return deltas_decode(state, pulled)


def deltas_pull(state):
    """Block until the dispatch's outputs are host arrays (None where
    nothing went to the device)."""
    outs = state[3]
    if outs is None:
        return None
    if hasattr(outs, "result"):
        # A background-thread pull started at dispatch time
        # (deltas_dispatch parks the blocking pull on the pull thread
        # so the transfer overlaps the caller's host work).
        return outs.result()
    return to_host_many(*outs)


@with_x64
def deltas_decode(state, pulled) -> Tuple[Dict[str, Dict[str, int]], int]:
    """Decode the pulled per-(owner, minute) deltas. If any shard
    produced more segments than the compaction cap, re-run the
    full-width kernel and decode every row (rare: means distinct
    (owner, minute) pairs exceed an eighth of the shard's rows)."""
    deltas, digest, good, _outs, extra = state
    if pulled is None:
        return deltas, digest
    buf, k1, oix, mesh, cap = extra
    n_dev = mesh.devices.size
    packed, xors, counts, dev_digest = _unpack_outputs(pulled[0], n_dev, cap)
    if (counts > cap).any():
        log("kernel:merkle", "segment compaction overflow: full-width pull",
            cap=cap, max_count=int(counts.max()))
        millis = (k1 >> np.uint64(16)).astype(np.int64)
        counter = (k1 & np.uint64(0xFFFF)).astype(np.int32)
        valid = oix >= 0
        shard_size = len(oix) // n_dev
        node = buf.reshape(n_dev, -1)[:, shard_size:2 * shard_size].reshape(-1)
        shd = sharding(mesh)
        args = [
            put_sharded(a, shd)
            for a in (millis, counter, node, valid,
                      np.maximum(oix, 0).astype(np.int64))
        ]
        owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted, dev_digest = (
            to_host_many(*_compiled_merkle_kernel(mesh)(*args))
        )
        by_ix = decode_owner_minute_deltas(
            owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted
        )
    else:
        from evolu_tpu.core.merkle import minutes_base3
        from evolu_tpu.core.murmur import to_int32

        by_ix: Dict[int, Dict[str, int]] = {}
        key_cache: Dict[int, str] = {}
        for si in range(n_dev):
            c = int(counts[si])
            for p, x in zip(packed[si, :c].tolist(), xors[si, :c].tolist()):
                o_ix = p >> 32
                minute = p & 0xFFFFFFFF
                if minute >= 1 << 31:  # undo the uint32 bit carriage of
                    minute -= 1 << 32  # the JS |0-wrapped int32 minute
                key = key_cache.get(minute)
                if key is None:
                    key = key_cache[minute] = minutes_base3(minute * 60000)
                d = by_ix.setdefault(o_ix, {})
                d[key] = to_int32(d.get(key, 0) ^ int(x))
    for o_ix, d in by_ix.items():
        deltas[good[o_ix]] = d
    return deltas, digest ^ int(dev_digest)


def _ledger_count_pass(requests, inserted_by_owner) -> None:
    """Conservation-ledger terminal classification for ONE committed
    engine pass: per owner, `inserted` rows were new (was-new flags /
    set-diff), and everything else the owner submitted this pass —
    including rows the in-batch dedup dropped before they reached a
    shard buffer — terminates at store.duplicate. Call ONLY after the
    shard transactions committed: a poisoned (rolled-back) pass must
    post nothing, or the scheduler's singleton retry would
    double-count."""
    totals: Dict[str, int] = {}
    for r in requests:
        if r.messages:
            totals[r.user_id] = totals.get(r.user_id, 0) + len(r.messages)
    for o, total in totals.items():
        ins = int(inserted_by_owner.get(o, 0))
        ledger.count(ledger.STORE_INSERTED, ins, owner=o)
        ledger.count(ledger.STORE_DUPLICATE, total - ins, owner=o)


def _count_pass(route: str, st) -> None:
    """One landing pass: the route it lands by (`_route`) and which
    body packed it in `start_batch` (`_pack_batch`), in one acquisition
    of the registry's lock. A pass that raised before it landed counts
    in neither."""
    metrics.inc_many((
        ("evolu_engine_store_passes_total", 1, {"path": route}),
        ("evolu_engine_pack_total", 1, {"path": st["pack_path"]}),
    ))


def _fold_trees(deltas_by_owner, stored, tree_rows, shard_index, trees, strings) -> None:
    """Fold each owner's pass deltas onto its stored tree TEXT
    (`_store_pass`'s `stored`): the folded tree into `trees`, its dump
    into `strings` and, as the (owner, TEXT) row `_store_pass` upserts,
    into the owner's shard's list of `tree_rows`."""
    for o, deltas in deltas_by_owner.items():
        if not deltas:
            continue
        trees[o] = apply_prefix_xors(merkle_tree_from_string(stored[o]), deltas)
        strings[o] = merkle_tree_to_string(trees[o])
        tree_rows[shard_index(o)].append((o, strings[o]))


def _pack_rows(ts_list, contents):
    """Pack one shard's rows into flat buffers. Per-string width check
    BEFORE packing: a total-length check alone would accept
    ["", "<two stamps concatenated>"] and commit rows with shifted
    timestamp/content pairing (same invariant as
    parse_timestamp_strings)."""
    n = len(ts_list)
    if (np.fromiter(map(len, ts_list), np.int64, count=n) != 46).any():
        raise ValueError("non-canonical timestamp width in batch")
    ts_packed = "".join(ts_list).encode("ascii")
    lens = np.fromiter(map(len, contents), np.int32, count=n)
    return ts_packed, b"".join(contents), lens


def _pack_shards_python(per_shard) -> Dict[int, tuple]:
    """The per-message half of `_pack_batch` in Python: the canonical
    body, which `pack_requests`' native walk reproduces byte for byte
    and demotes to for anything but exact 46-character ASCII `str`
    timestamps and exact `bytes` contents, so that every such batch
    raises here what it always raised. `per_shard[si]`: the requests of
    shard si in request order. → {si: (owners, kept rows of each, packed
    timestamps, packed contents, content lengths)} for the shards with
    a row, in shard order; a request whose rows the dedup all dropped
    gives no group."""
    seen: set = set()
    shard_data: Dict[int, tuple] = {}
    for si, reqs in enumerate(per_shard):
        gu: List[str] = []
        gc: List[int] = []
        ts_list: List[str] = []
        contents: List[bytes] = []
        for r in reqs:
            # In-batch dedup up front: correction logic needs
            # was_new==False to mean exactly "already in the
            # store". Same-user rows stay in request order, so the
            # kept occurrence matches the row the PK would keep.
            kept = [
                m for m in r.messages
                if (m.timestamp, r.user_id) not in seen
                and not seen.add((m.timestamp, r.user_id))
            ]
            if kept:
                gu.append(r.user_id)
                gc.append(len(kept))
                ts_list.extend(m.timestamp for m in kept)
                contents.extend(m.content for m in kept)
        if ts_list:
            shard_data[si] = (gu, gc, *_pack_rows(ts_list, contents))
    return shard_data


class _PackedRows:
    """Lazy timestamp-string accessor over per-shard packed 46-byte
    buffers (used only for the rare non-canonical host fold)."""

    def __init__(self, buffers: List[bytes], offsets: List[int]):
        self._buffers = buffers
        self._offsets = offsets

    def __getitem__(self, i: int) -> str:
        import bisect

        j = bisect.bisect_right(self._offsets, i) - 1
        local = i - self._offsets[j]
        return self._buffers[j][local * 46 : (local + 1) * 46].decode("ascii")


class BatchReconciler:
    """Reconcile a batch of SyncRequests against one RelayStore or a
    ShardedRelayStore (parallel per-shard ingest).

    With a `write_behind` queue attached (PR-11,
    `storage/write_behind.py`), `run_batch_wire` serves from
    device-derived in-memory state instead: the batch's Merkle deltas
    fold onto per-owner authoritative trees held by the queue, the
    packed row buffers are ACKed into the durable log, and SQLite
    materialization happens on the queue's background drain thread —
    off the serving path. Responses that need stored MESSAGES (a
    non-empty tree diff) wait on the owner's drain watermark first, so
    every byte served from SQLite is committed state. The offline
    entry points (`reconcile*`) stay synchronous — deferral is a
    property of the live serving path only."""

    def __init__(
        self, store, mesh: Optional[Mesh] = None, write_behind=None, mesh_ctx=None
    ):
        self.store = store
        # PR-12 sharded-engine path: a parallel.mesh.MeshContext pins
        # the mesh AND switches every device layout this reconciler
        # builds to stable owner→device placement (deltas_dispatch's
        # `ctx=` leg). None = the per-batch LPT layout (the default
        # until the parity gate is green in a deployment —
        # Config.mesh_engine).
        self.mesh_ctx = mesh_ctx
        if mesh_ctx is not None and mesh is None:
            mesh = mesh_ctx.mesh
        self.mesh = mesh or create_mesh()
        self.write_behind = write_behind
        self._pull_pool = None
        self._stage_pool = None
        # `pack_requests`' dedup table and row index, kept from pass to
        # pass so that a pass's pack touches no fresh pages for them.
        self._pack_scratch = None

    def _new_messages(
        self, requests: Sequence[protocol.SyncRequest]
    ) -> Dict[str, List[protocol.EncryptedCrdtMessage]]:
        """Bulk dedup: which (timestamp, userId) pairs are not yet stored.
        Batch equivalent of per-row INSERT OR IGNORE changes==1
        (index.ts:153-158). Duplicates inside the batch dedup here too."""
        db = self.store.db
        seen: set = set()
        incoming: List[Tuple[str, str, protocol.EncryptedCrdtMessage]] = []
        for r in requests:
            for m in r.messages:
                k = (m.timestamp, r.user_id)
                if k not in seen:
                    seen.add(k)
                    incoming.append((m.timestamp, r.user_id, m))
        if not incoming:
            return {}
        with db.transaction():
            db.exec('CREATE TEMP TABLE IF NOT EXISTS "__incoming" ("t" TEXT, "u" TEXT)')
            db.run('DELETE FROM "__incoming"')
            db.run_many('INSERT INTO "__incoming" VALUES (?, ?)', [(t, u) for t, u, _ in incoming])
            rows = db.exec_sql_query(
                'SELECT i."t" AS t, i."u" AS u FROM "__incoming" i '
                'JOIN "message" m ON m."timestamp" = i."t" AND m."userId" = i."u"'
            )
            db.run('DELETE FROM "__incoming"')
        existing = {(r["t"], r["u"]) for r in rows}
        out: Dict[str, List[protocol.EncryptedCrdtMessage]] = {}
        for t, u, m in incoming:
            if (t, u) not in existing:
                out.setdefault(u, []).append(m)
        return out

    def reconcile(
        self, requests: Sequence[protocol.SyncRequest]
    ) -> List[protocol.SyncResponse]:
        """One batched pass; responses align with `requests` order.
        End state is identical to running `store.sync` per request."""
        trees, strings = self._ingest(requests)
        return self._respond(requests, trees, strings)

    def _route(self, live: bool) -> str:
        """Where a pass lands: the ONE route decision, read by `_ingest`
        and `run_batch_wire`, and the `path` label of
        `evolu_engine_store_passes_total`. "write_behind": a queue is
        attached and the pass is `live` (the scheduler's; the offline
        entry points stay synchronous). "stream": the store says it is
        packed-capable (`start_batch` + `_land`; a stand-in store
        without the attribute answers no). "generic": everything else,
        the python-backend routes under `_ingest`."""
        if live and self.write_behind is not None and hasattr(
            self.store, "get_merkle_tree_string"
        ):
            return "write_behind"
        if getattr(self.store, "packed", False):
            return "stream"
        return "generic"

    def _ingest(self, requests, respond_stage=None):
        """The synchronous batched ingest → (trees, strings). ONE copy
        shared by `reconcile` and `reconcile_wire`. `respond_stage`: see
        `finish_batch`."""
        if self._route(live=False) == "stream":
            return self._land(self.start_batch(requests), respond_stage)
        metrics.inc("evolu_engine_store_passes_total", path="generic")
        strings: Dict[str, str] = {}
        if (not isinstance(self.store, ShardedRelayStore)
                and getattr(self.store, "db", None) is not None):
            trees = self._ingest_generic(requests, strings)
        else:
            # Sharded python-backend, or a generic store (RelayStore
            # surface, no `.db` SQL handle): per-request ingest; the
            # respond side degrades likewise (`_respond_wire`'s object
            # fallback).
            trees = {
                r.user_id: self.store.add_messages(r.user_id, r.messages)
                for r in requests
            }
        if respond_stage is not None:
            respond_stage.start()
        return trees, strings

    def _shards(self):
        if isinstance(self.store, ShardedRelayStore):
            return self.store.shards, self.store.shard_index
        return [self.store], (lambda _u: 0)

    def _pull_executor(self):
        if self._pull_pool is None:
            self._pull_pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="evolu-pull")
        return self._pull_pool

    def _stage_executor(self):
        """The ONE helper thread of `reconcile_stream`: it runs
        `start_batch` of the next pass and nothing else, never two at
        once, and never touches the store."""
        if self._stage_pool is None:
            self._stage_pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="evolu-stage")
        return self._stage_pool

    @contextmanager
    def _store_pass(self, stores, live, batches, entering=None):
        """The storage leg of ONE pass over the live shards, in two
        native calls (`storage/native.py`, the shard-set calls) and no
        thread of this process's but the caller's: `reconcile_stream`'s
        helper stages the next pass beside the first call (`entering`,
        handed to it, is what lets the helper go) and never enters
        here. On entry: BEGIN +
        the packed INSERT OR IGNORE of `batches[i]` on shard `live[i]`
        + the stored trees of every group user, one call. Yields
        (was-new flags by shard id, {owner: stored tree TEXT}, per-shard
        lists for the caller to fill with (owner, tree TEXT) rows). On
        exit: those rows upserted and every shard committed, one call.
        Insert and upsert of a shard share a transaction, so rows never
        outrun their tree; an exception anywhere, in the body too,
        rolls back EVERY live shard before it propagates."""
        dbs = [stores[si].db for si in live]
        flags, stored = relay_insert_packed_shards(
            dbs, batches,
            also_count=(("evolu_engine_store_calls_total", 1, {"op": "insert"}),),
            entering=entering)
        tree_rows: List[List[Tuple[str, str]]] = [[] for _ in stores]
        try:
            yield dict(zip(live, flags)), stored, tree_rows
            relay_commit_shards(dbs, [tree_rows[si] for si in live])
        except BaseException:
            for db in dbs:
                db.rollback()
            raise
        metrics.inc("evolu_engine_store_calls_total", op="commit")

    @contextmanager
    def _shard_transactions(self, stores, live):
        """One open transaction per live shard, rolled back together on
        error, committed together on exit (first commit error wins):
        `_store_pass` for stores the shard-set calls cannot drive (the
        stdlib backend under `reconcile_pod`)."""
        begun: List[int] = []
        try:
            for si in live:
                stores[si].db.begin()
                begun.append(si)
            yield
        except BaseException:
            for si in begun:
                stores[si].db.rollback()
            raise
        commit_err: Optional[Exception] = None
        for si in begun:
            try:
                stores[si].db.commit()
            except Exception as e:  # noqa: BLE001
                commit_err = commit_err or e
        if commit_err is not None:
            raise commit_err

    def close(self) -> None:
        # The helper first: a pass it is still staging submits its pull.
        if self._stage_pool is not None:
            self._stage_pool.shutdown(wait=True)
            self._stage_pool = None
        if self._pull_pool is not None:
            self._pull_pool.shutdown(wait=True)
            self._pull_pool = None

    # -- the packed ingest: start_batch, then _land --
    #
    # The device leg needs nothing from the database: it hashes the
    # WHOLE batch optimistically (newness is unknown until the insert),
    # and owners that turn out to contain duplicate rows get their
    # deltas recomputed host-side from the new rows only. So the insert
    # runs while the device computes, and in `reconcile_stream` ALL of
    # batch k+1's `start_batch` (pack, parse, layout, device call, and
    # behind them the device's compute and transfer) runs on the helper
    # thread while batch k's C inserts run on the caller's: the insert
    # is one native call that holds no interpreter lock, and
    # `start_batch` never calls SQLite.

    def start_batch(self, requests: Sequence[protocol.SyncRequest]):
        """Stage batch k+1: pack per-shard buffers, parse natively,
        dispatch the device hash of ALL rows, START the async output
        transfer. No database access happens here. The whole seam is
        one `device_dispatch` stage record (obs.anatomy): the fixed
        per-dispatch cost separates from the per-row slope in the stage
        fit, and where the device has a priced pipeline floor a
        dispatch above FLOOR_FACTOR× it flags
        evolu_stage_over_floor_total. Four children tile it seam to
        seam, each recorded once per pass: `pass_pack` (`_pack_batch`),
        `pass_parse` (`parse_packed_timestamps` of every live shard),
        and `deltas_dispatch`'s `pass_layout` + `pass_device_call`."""
        with anatomy.stage("device_dispatch", cpu=False) as whole, \
                anatomy.stage("pass_pack") as tile:
            live, shard_data, packed, shard_offsets, merged, off, pack_path = \
                self._pack_batch(requests)
            whole.rows = tile.rows = off
            # Parse AFTER every shard packed (it needs only the packed
            # buffer), so each stage is one interval a pass.
            tile.then("pass_parse", rows=off)
            col_parts = ([], [], [], [])
            for si in live:
                _gu, gc, ts_packed, _cp, _lens = shard_data[si]
                cols = parse_packed_timestamps(ts_packed, sum(gc), with_case=True)
                for part, c in zip(col_parts, cols):
                    part.append(c)
            dev_state = None
            if merged:
                all_m, all_c, all_n, case_ok = (
                    (p[0] if len(p) == 1 else np.concatenate(p)) for p in col_parts
                )
                # The blocking pull starts on the pull thread inside the
                # dispatch: the device/host overlap of the pipelined path.
                dev_state = deltas_dispatch(
                    self.mesh, merged, all_m, all_c, all_n, case_ok, packed,
                    ctx=self.mesh_ctx, pull_pool=self._pull_executor(), tile=tile,
                )
        return {
            "requests": requests, "live": live, "shard_data": shard_data,
            "dev": dev_state, "packed": packed, "n_total": off,
            "shard_offsets": shard_offsets, "pack_path": pack_path,
        }

    def _pack_batch(self, requests):
        """The `pass_pack` leg of `start_batch`: shard grouping, then
        what is per message (the in-batch dedup, the packed buffers of
        every shard) in one native walk, `_pack_shards_native`, or,
        where that declines the batch, in `_pack_shards_python`, which
        owns the error surface; then the owner index arrays. →
        (live shard ids, per-shard packed data, `_PackedRows`, shard row
        offsets, owner → row indices, row count, which of the two
        packed: `evolu_engine_pack_total`'s `path`)."""
        stores, shard_index = self._shards()
        per_shard: List[List[protocol.SyncRequest]] = [[] for _ in stores]
        for r in requests:
            per_shard[shard_index(r.user_id)].append(r)
        path = "native"
        shard_data = self._pack_shards_native(per_shard)
        if shard_data is None:
            path = "python"
            shard_data = _pack_shards_python(per_shard)

        buffers: List[bytes] = []
        offsets: List[int] = []
        owner_rows: Dict[str, List[np.ndarray]] = {}
        # One arange a pass, sliced a request: `np.arange` drops the
        # interpreter lock whatever its length, and on the dispatcher
        # thread every drop is a turn lost to the handler threads.
        rows = np.arange(sum(len(d[4]) for d in shard_data.values()))
        off = 0
        for gu, gc, ts_packed, _cp, lens in shard_data.values():
            pos = off
            for u, k in zip(gu, gc):
                owner_rows.setdefault(u, []).append(rows[pos:pos + k])
                pos += k
            buffers.append(ts_packed)
            offsets.append(off)
            off += len(lens)
        merged = {
            u: (v[0] if len(v) == 1 else np.concatenate(v))
            for u, v in owner_rows.items()
        }
        live = list(shard_data)
        return (live, shard_data, _PackedRows(buffers, offsets),
                dict(zip(live, offsets)), merged, off, path)

    def _pack_shards_native(self, per_shard) -> Optional[Dict[int, tuple]]:
        """`_pack_shards_python`'s result from `pack_requests`' one
        walk (ops/host_parse.py), or None where it declines. What is
        per request stays here: the groups in shard order, an owner's
        dense id, and the (owner, kept rows) lists of each shard."""
        groups: List[Sequence] = []
        users: List[str] = []
        owner_ids: Dict[str, int] = {}
        shard_groups: List[int] = []
        for reqs in per_shard:
            before = len(groups)
            for r in reqs:
                if r.messages:
                    groups.append(r.messages)
                    users.append(r.user_id)
                    owner_ids.setdefault(r.user_id, len(owner_ids))
            shard_groups.append(len(groups) - before)
        packed = pack_requests(
            groups, map(owner_ids.__getitem__, users), shard_groups,
            self._pack_scratch)
        if packed is None:
            return None
        kept, lens, buffers, self._pack_scratch = packed
        kept = kept.tolist()
        buffers = iter(buffers)
        shard_data: Dict[int, tuple] = {}
        g = row = 0
        for si, n_groups in enumerate(shard_groups):
            gu = [u for u, k in zip(users[g:g + n_groups], kept[g:g + n_groups]) if k]
            gc = [k for k in kept[g:g + n_groups] if k]
            g += n_groups
            n = sum(gc)
            if n:
                shard_data[si] = (gu, gc, next(buffers), next(buffers),
                                  lens[row:row + n])
                row += n
        return shard_data

    def finish_batch(self, st, wire: bool = False, respond_stage=None,
                     entering=None) -> List:
        """Land batch k (`_land`) and answer it — while batch k+1 flies
        on the device, or in `reconcile_stream` is staged on the helper
        (`entering`: see `_land`). `wire=True` answers in BYTES mode
        (`_respond_wire`) for consumers that only forward protobuf,
        byte-identical to encoding the object responses (test-pinned
        via `_respond_wire`'s own fence). A `respond_stage` (an
        unstarted `anatomy.stage`, the scheduler's `pass_respond`) is
        STARTED where the apply leg ends; the caller, on this thread,
        stops it when its own respond work is done."""
        trees, strings = self._land(st, respond_stage, entering)
        respond = self._respond_wire if wire else self._respond
        return respond(st["requests"], trees, strings)

    def _land(self, st, respond_stage=None, entering=None):
        """The landing half of a packed pass → (trees, strings): the C
        inserts of every live shard in one native call,
        duplicate-owner delta recompute, tree updates, one atomic
        commit per shard in a second (`_store_pass`), then the ledger
        count. `respond_stage`: see `finish_batch`. `entering`: called
        on this thread as the insert call gives up the interpreter lock
        (`relay_insert_packed_shards`); not called where the pass has
        no live shard or raises before that."""
        stores, shard_index = self._shards()
        _count_pass("stream", st)
        live, shard_data = st["live"], st["shard_data"]
        trees: Dict[str, dict] = {}
        strings: Dict[str, str] = {}
        if not live:
            if respond_stage is not None:
                respond_stage.start()
            return trees, strings

        # host_apply stage record (obs.anatomy): the WHOLE landing leg
        # up to the commit — C inserts, the blocked wait for the pull,
        # delta decode, tree folds, commit. The `kernel:merkle` span
        # below has the same extent under its historical name: it times
        # this host leg, not a kernel. Three children tile it:
        # pass_insert (`_store_pass`'s first native call: BEGIN, the C
        # inserts, the stored trees), pass_pull_wait (only the blocking
        # wait for the device's outputs), pass_tree (decode, duplicate
        # recompute, tree folds, and the second native call: merkleTree
        # upsert + COMMIT). The pull itself records under pull_wave from
        # to_host_many on the pull thread — shares are over summed stage
        # walls, and the two legs overlap (docs/OBSERVABILITY.md).
        rows = st["n_total"]
        with anatomy.stage("host_apply", rows=rows, cpu=False), \
                span("kernel:merkle", "reconcile_stream_finish",
                     owners=len({r.user_id for r in st["requests"]}),
                     n=rows, shards=len(live)), \
                anatomy.stage("pass_insert", rows=rows) as tile:
            with self._store_pass(
                    stores, live, [shard_data[si] for si in live], entering) as (
                    was_new_by_shard, stored, tree_rows):
                tile.then("pass_pull_wait")
                pulled = deltas_pull(st["dev"])
                tile.then("pass_tree", rows=rows)
                deltas_by_owner, _digest = deltas_decode(st["dev"], pulled)
                self._recompute_duplicate_owners(
                    st, was_new_by_shard, deltas_by_owner
                )
                _fold_trees(deltas_by_owner, stored, tree_rows, shard_index, trees, strings)
        if respond_stage is not None:
            respond_stage.start()
        # Ledger terminals AFTER the per-shard commits: per-owner
        # was-new sums classify inserted; the per-owner request totals
        # in _ledger_count_pass fold the in-batch-deduped rows into
        # store.duplicate automatically.
        ins_by_owner: Dict[str, int] = {}
        for si in live:
            gu, gc, _tsp, _cp, _lens = shard_data[si]
            was_new = was_new_by_shard[si]
            pos = 0
            for u, k in zip(gu, gc):
                ins_by_owner[u] = ins_by_owner.get(u, 0) + int(
                    np.asarray(was_new[pos : pos + k]).sum()
                )
                pos += k
        _ledger_count_pass(st["requests"], ins_by_owner)
        return trees, strings

    def _recompute_duplicate_owners(self, st, was_new_by_shard, deltas_by_owner) -> None:
        """The device hashed every row; owners where some rows were
        already stored get their delta dict recomputed from the NEW
        rows only — the fold `RelayStore.add_messages` runs, so minute-key
        presence semantics (a minute whose new hashes XOR to zero stays
        present; a minute with only duplicate rows disappears) are
        bit-identical. Steady state has no duplicates and skips this
        entirely; a full-replay batch has no new rows and recomputes
        empty dicts — both ends are cheap."""
        from evolu_tpu.core.merkle import minute_deltas_host

        packed = st["packed"]
        offsets = st["shard_offsets"]
        # Pass 1 (steady state exits here): which owners have ANY
        # duplicate row? One cheap .all() per group, no allocations.
        affected: set = set()
        for si in st["live"]:
            gu, gc, _tsp, _cp, _lens = st["shard_data"][si]
            was_new = was_new_by_shard[si]
            pos = 0
            for u, k in zip(gu, gc):
                if not was_new[pos : pos + k].all():
                    affected.add(u)
                pos += k
        if not affected:
            return
        # Pass 2: an affected owner needs ALL its new rows (it may span
        # several request groups) — collect, then recompute once.
        new_rows: Dict[str, List[np.ndarray]] = {}
        for si in st["live"]:
            gu, gc, _tsp, _cp, _lens = st["shard_data"][si]
            was_new = was_new_by_shard[si]
            base = offsets[si]
            pos = 0
            for u, k in zip(gu, gc):
                if u in affected:
                    new_rows.setdefault(u, []).append(
                        np.nonzero(was_new[pos : pos + k])[0] + (pos + base)
                    )
                pos += k
        for u in affected:
            ix = np.concatenate(new_rows[u])
            deltas_by_owner[u], _d = minute_deltas_host(packed[i] for i in ix)

    def reconcile_stream(
        self, batches: Sequence[Sequence[protocol.SyncRequest]]
    ) -> List[List[protocol.SyncResponse]]:
        """Software-pipelined reconcile over a stream of request
        batches: while this thread lands batch k (`finish_batch`: C
        inserts, pull wait, trees, commit, answers), the engine's one
        helper thread stages batch k+1 (`start_batch`: pack, parse,
        layout, device call, and behind them the device's hash and the
        output transfer). The stream is iterated, every SQLite call is
        made and every answer is built here, in stream order; the first
        batch is staged here too, so a stream of one starts no thread.
        At most one batch is staged ahead. `pass_stage_join` is what the
        landing thread then still waits for the staging. End state is
        identical to sequential `reconcile` calls, which is what any
        other route than the packed one runs."""
        if self._route(live=False) != "stream":
            return [self.reconcile(b) for b in batches]
        out: List[List[protocol.SyncResponse]] = []
        prev = None
        for reqs in batches:
            if prev is None:
                prev = self.start_batch(reqs)
                metrics.inc("evolu_engine_stream_staged_total", thread="caller")
                continue
            # The helper parks on `go` until the landing has entered its
            # native insert: let go any earlier it can win the
            # interpreter lock and hold it through the whole native
            # pack, in front of the insert.
            go = threading.Event()
            staging = self._stage_executor().submit(
                contextvars.copy_context().run, self._stage_behind, go, reqs)
            try:
                out.append(self.finish_batch(prev, entering=go.set))
            except BaseException:
                # The landing's exception, not the helper's: wait for
                # the helper, drop what it staged once its pull is in.
                go.set()
                if staging.exception() is None:
                    dev = staging.result()["dev"]
                    if dev is not None and dev[3] is not None:
                        dev[3].exception()  # waits; the pull's own failure is dropped too
                raise
            go.set()  # a landing without live shards never reached the insert
            # A bad batch k+1 surfaces here, after batch k has landed
            # and answered: sequential reconcile would have committed k
            # before raising.
            with anatomy.stage("pass_stage_join", cpu=False):
                prev = staging.result()
            metrics.inc("evolu_engine_stream_staged_total", thread="helper")
        if prev is not None:
            out.append(self.finish_batch(prev))
        return out

    def _stage_behind(self, go, requests):
        """The helper's whole job: wait, off the interpreter lock, for
        the landing thread to let go, then `start_batch`."""
        go.wait()
        return self.start_batch(requests)

    def _ingest_generic(self, requests, tree_strings=None) -> Dict[str, dict]:
        """Python-backend fallback: temp-table set-diff + bulk SQL."""
        new_by_owner = self._new_messages(requests)

        # Device: per-(owner, minute) XOR deltas for all new timestamps.
        deltas_by_owner, _digest = (
            owner_minute_deltas(
                self.mesh,
                {o: [m.timestamp for m in ms] for o, ms in new_by_owner.items()},
                ctx=self.mesh_ctx,
            )
            if new_by_owner
            else ({}, 0)
        )

        # Host: bulk insert + tree updates in one transaction.
        db = self.store.db
        trees: Dict[str, dict] = {}
        with db.transaction():
            rows = [
                (m.timestamp, o, m.content)
                for o, ms in new_by_owner.items()
                for m in ms
            ]
            if rows:
                db.run_many(
                    'INSERT OR IGNORE INTO "message" ("timestamp", "userId", "content") '
                    "VALUES (?, ?, ?)",
                    rows,
                )
            for o, deltas in deltas_by_owner.items():
                tree = apply_prefix_xors(self.store.get_merkle_tree(o), deltas)
                trees[o] = tree
                s = merkle_tree_to_string(tree)
                if tree_strings is not None:
                    tree_strings[o] = s
                db.run(
                    'INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") VALUES (?, ?)',
                    (o, s),
                )
        _ledger_count_pass(
            requests, {o: len(ms) for o, ms in new_by_owner.items()}
        )
        return trees

    def _resolve_tree(self, user_id: str, trees, tree_strings):
        """Tree + serialized string for one owner, reusing the ingest's
        caches; owners not in `trees` (no new rows this batch — the
        cold-sync shape) read the STORED string verbatim and parse it
        once for the diff, never re-dumping (the parse→re-dump
        round-trip, ~1.25 ms per realistic owner tree, was the measured
        respond wall at 1k divergent owners — docs/BENCHMARKS.md r4).
        Mutates both caches; ONE copy shared by `_respond` and
        `_respond_wire`."""

        tree = trees.get(user_id)
        if tree is None:
            if hasattr(self.store, "get_merkle_tree_string"):
                raw = self.store.get_merkle_tree_string(user_id)
                tree = merkle_tree_from_string(raw)
            else:
                tree = self.store.get_merkle_tree(user_id)
                raw = merkle_tree_to_string(tree)
            trees[user_id] = tree
            tree_strings.setdefault(user_id, raw)
        raw = tree_strings.get(user_id)
        if raw is None:
            raw = tree_strings[user_id] = merkle_tree_to_string(tree)
        return tree, raw

    def _respond(
        self, requests, trees: Dict[str, dict],
        tree_strings: Optional[Dict[str, str]] = None,
    ) -> List[protocol.SyncResponse]:
        """Standard diff per request against the updated trees."""

        responses = []
        tree_strings = dict(tree_strings or {})
        for r in requests:
            if r.scope is not None:
                # Scoped request: the batch ingest above already landed
                # its rows in the FULL tree (scoping never touches
                # ingest); only the respond is answered from the
                # derived scoped subtree (server/scope.py).
                from evolu_tpu.server import scope as scope_mod

                responses.append(scope_mod.scoped_response(self.store, r))
                continue
            tree, ts = self._resolve_tree(r.user_id, trees, tree_strings)
            client_tree = merkle_tree_from_string(r.merkle_tree)
            messages = self.store.get_messages(r.user_id, r.node_id, tree, client_tree)
            responses.append(protocol.SyncResponse(messages, ts))
        return responses

    def reconcile_wire(
        self, requests: Sequence[protocol.SyncRequest], respond_stage=None
    ) -> List[bytes]:
        """`reconcile` with BYTES-mode responses: each entry is the
        fully encoded SyncResponse, the messages stream emitted
        straight from C (`eh_get_messages_wire`) — for consumers that
        only forward protobuf (the HTTP/pod serve paths), where the
        per-message SyncResponse objects of `_respond` were pure
        retention cost (docs/BENCHMARKS.md r4: the divergent respond
        leg was ~196k msgs/s object-bound while the relay's identical
        C leg served 1.39M). Byte-identical to
        `encode_sync_response(reconcile(...)[i])` (test-pinned);
        per-request fallback to the object path + encoder where the C
        entry is missing or a stored row is non-canonical."""
        trees, strings = self._ingest(requests, respond_stage)
        return self._respond_wire(requests, trees, strings)

    def run_batch_wire(self, requests: Sequence[protocol.SyncRequest],
                       respond_stage=None) -> List[bytes]:
        """ONE engine/store pass for a live micro-batch → wire bytes per
        request (the scheduler's entry point). With a write-behind
        queue attached, the pass defers SQLite entirely
        (`_finish_batch_deferred`): serve from in-memory trees, ACK
        into the durable log, answer — a `WriteBehindFull` raised
        before the ACK leaves no state anywhere (the scheduler maps it
        to 503 + Retry-After). Otherwise it is `reconcile_wire`, whose
        `_ingest` reads the same `_route`: `start_batch` + `_land` on a
        packed-capable store (in-batch dedup in request order,
        optimistic device hash, atomic per-shard insert+tree commit),
        the python-backend routes elsewhere. Either way a failure
        rolls every shard transaction back before raising — the
        scheduler's singleton retry depends on that. `respond_stage`:
        see `finish_batch`; every route starts it where its respond
        leg begins."""
        if self._route(live=True) == "write_behind":
            return self._finish_batch_deferred(
                self.start_batch(requests), respond_stage)
        return self.reconcile_wire(requests, respond_stage)

    # -- write-behind serving (PR-11: device state is the truth) --

    def _finish_batch_deferred(self, st, respond_stage=None) -> List[bytes]:
        """Land batch k WITHOUT touching the btree: fold the device
        deltas onto the queue's authoritative per-owner trees
        (optimistically — every in-batch-deduped row XORs; rows that
        turn out to be already stored are corrected EXACTLY at drain
        time, see storage/write_behind.py), append the packed row
        buffers + tree strings to the durable log (the ACK point), and
        respond from the in-memory trees. Nothing is installed if the
        append raises (backpressure or log failure) — the serving
        state stays consistent for the retry."""
        from evolu_tpu.storage.write_behind import IngestRecord

        wb = self.write_behind
        requests = st["requests"]
        live, shard_data = st["live"], st["shard_data"]
        trees: Dict[str, dict] = {}
        strings: Dict[str, str] = {}
        _count_pass("write_behind", st)
        if not live:
            if respond_stage is not None:
                respond_stage.start()
            return self._respond_deferred(requests, trees, strings)
        with span("kernel:merkle", "reconcile_deferred",
                  owners=len({r.user_id for r in requests}),
                  n=st["n_total"], shards=len(live)):
            deltas_by_owner, _digest = deltas_finish(st["dev"])
            for o, deltas in deltas_by_owner.items():
                if not deltas:
                    continue
                cached = wb.serving_tree(o)
                if cached is not None:
                    base_tree = cached[0]
                else:
                    # Per-owner read: only the owner's SHARD lock — a
                    # sibling shard's drain keeps running underneath.
                    with wb.owner_lock(o):
                        raw = self.store.get_merkle_tree_string(o)
                    base_tree = merkle_tree_from_string(raw)
                tree = apply_prefix_xors(base_tree, deltas)
                trees[o] = tree
                strings[o] = merkle_tree_to_string(tree)
            records = []
            for si in live:
                gu, gc, ts_packed, content_packed, lens = shard_data[si]
                seen_o: set = set()
                tree_rows = []
                for o in gu:
                    if o in strings and o not in seen_o:
                        seen_o.add(o)
                        tree_rows.append((o, strings[o]))
                records.append(IngestRecord(
                    gu, gc, ts_packed, content_packed, lens, tree_rows
                ))
            wb.append_batch(
                records, {o: (trees[o], strings[o]) for o in strings}
            )
            # Ledger: append_batch counted wb.queued for every row that
            # entered the log (the ACK); rows the in-batch dedup
            # dropped never reach the queue and terminate HERE as
            # store.duplicate. The queued rows' inserted/duplicate
            # split is classified exactly at drain time, per shard
            # (write_behind._materialize_shard) — nothing is posted if the
            # append raised (backpressure = no state anywhere).
            kept: Dict[str, int] = {}
            for si in live:
                gu, gc, _tsp, _cp, _lens = shard_data[si]
                for u, k in zip(gu, gc):
                    kept[u] = kept.get(u, 0) + k
            totals: Dict[str, int] = {}
            for r in requests:
                if r.messages:
                    totals[r.user_id] = totals.get(r.user_id, 0) + len(r.messages)
            for o, total in totals.items():
                ledger.count(ledger.STORE_DUPLICATE, total - kept.get(o, 0),
                             owner=o)
        if respond_stage is not None:
            respond_stage.start()
        return self._respond_deferred(requests, trees, strings)

    def _resolve_tree_deferred(self, user_id: str, trees, tree_strings):
        """`_resolve_tree` against the write-behind truth: this batch's
        freshly folded tree, else the queue's serving cache (the owner
        has undrained history), else the stored string (SQLite is
        current for fully drained owners)."""

        tree = trees.get(user_id)
        if tree is not None:
            return tree, tree_strings[user_id]
        cached = self.write_behind.serving_tree(user_id)
        if cached is not None:
            tree, raw = cached
        else:
            with self.write_behind.owner_lock(user_id):
                raw = self.store.get_merkle_tree_string(user_id)
            tree = merkle_tree_from_string(raw)
        trees[user_id] = tree
        tree_strings[user_id] = raw
        return tree, raw

    def _respond_deferred(self, requests, trees, strings) -> List[bytes]:
        """Bytes-mode respond for the deferred path. The hot shape —
        trees agree after the push — answers tree-only from memory
        (ZERO SQLite). A non-empty diff needs stored messages: wait on
        the owner's drain watermark, re-read the owner's EXACT
        committed tree, and run the SAME `fetch_response_stream`
        composition (the one byte-format-coupled copy, shared with
        `sync_wire` and `_respond_wire`) under the drain lock.

        The exact re-read matters beyond precision: a duplicate-
        carrying push folds an already-stored row's hash onto a base
        that contains it (XOR-cancel), so the OPTIMISTIC tree claims
        the row is missing. Serving that tree would make the client
        re-send the row every round — each redelivery re-cancelling it
        — a permanent retry livelock. Post-flush SQLite carries the
        drain-corrected truth, so the served tree converges instead
        (review finding, pinned by
        test_write_behind.py::test_duplicate_retry_response_tree_is_exact).
        Shards that cannot C-serve degrade to the batched object
        respond, also post-flush."""
        from evolu_tpu.core.merkle import diff_merkle_trees
        from evolu_tpu.core.types import NonCanonicalStoreError

        wb = self.write_behind
        shards, shard_ix = self._shards()
        out: List[Optional[bytes]] = []
        fallback: List[Tuple[int, protocol.SyncRequest]] = []
        for i, r in enumerate(requests):
            if r.scope is not None:
                # A scoped respond reads stored rows + lanes: SQLite
                # must be current for this owner first, and the serve
                # runs under the drain lock against committed truth.
                from evolu_tpu.server import scope as scope_mod

                wb.flush_owner(r.user_id)
                with wb.owner_lock(r.user_id):
                    out.append(protocol.encode_sync_response(
                        scope_mod.scoped_response(self.store, r)))
                continue
            tree, raw = self._resolve_tree_deferred(r.user_id, trees, strings)
            client_tree = merkle_tree_from_string(r.merkle_tree)
            if diff_merkle_trees(tree, client_tree) is None:
                out.append(protocol._string(2, raw))
                continue
            # The response needs stored rows: SQLite must be current
            # for this owner first (the per-owner drain watermark —
            # ONLY the owner's shard; a backlogged sibling shard
            # cannot stall this serve), and from here on the EXACT
            # committed tree serves under the owner's shard lock.
            wb.flush_owner(r.user_id)
            with wb.owner_lock(r.user_id):
                raw = self.store.get_merkle_tree_string(r.user_id)
            tree = merkle_tree_from_string(raw)
            trees[r.user_id] = tree
            strings[r.user_id] = raw
            if diff_merkle_trees(tree, client_tree) is None:
                # The optimistic divergence was the duplicate-cancel
                # artifact; the committed trees actually agree.
                out.append(protocol._string(2, raw))
                continue
            db = getattr(shards[shard_ix(r.user_id)], "db", None)
            if db is None or not hasattr(db, "fetch_relay_messages_wire"):
                fallback.append((i, r))
                out.append(None)
                continue
            try:
                with wb.owner_lock(r.user_id):
                    stream = fetch_response_stream(
                        db, r.user_id, r.node_id, tree, client_tree
                    )
            except NonCanonicalStoreError:
                fallback.append((i, r))
                out.append(None)
                continue
            out.append(stream + protocol._string(2, raw))
        if fallback:
            # Mixed-owner object-path respond: the one deferred-mode
            # site that still needs the whole-store composite lock.
            with wb.db_lock:
                resps = self._respond([r for _i, r in fallback], trees, strings)
            for (i, _r), resp in zip(fallback, resps):
                out[i] = protocol.encode_sync_response(resp)
        return out

    def _respond_wire(
        self, requests, trees: Dict[str, dict],
        tree_strings: Optional[Dict[str, str]] = None,
    ) -> List[bytes]:
        """Bytes-mode twin of `_respond`. The response composition is
        `store.fetch_response_stream`'s two halves (`response_since`,
        then the C fetch; the same rule as `RelayStore.sync_wire`)
        plus the field-2 tree string — the SAME serialized tree
        `_respond` would carry, so encodings are byte-identical.
        Requests a shard cannot C-serve (python backend, malformed
        stored row) degrade to ONE batched object-path respond at
        their original positions.

        Two children of `pass_respond` split the per-request loop:
        `respond_diff` (client-tree parse + diff + the `since` string)
        and `respond_fetch` (the C fetch), each the SUM over the
        call's requests and ONE `evolu_stage_ms` observation, posted
        with what the answers held (`evolu_engine_respond_*`) in one
        `metrics.observe_many` a call (docs/OBSERVABILITY.md)."""
        from evolu_tpu.core.types import NonCanonicalStoreError

        shards, shard_ix = self._shards()
        tree_strings = dict(tree_strings or {})
        out: List[Optional[bytes]] = []
        fallback: List[Tuple[int, protocol.SyncRequest]] = []
        diffing = anatomy.summed_stage("respond_diff")
        fetching = anatomy.summed_stage("respond_fetch")
        held = [0] * len(requests)  # messages in each answer
        for i, r in enumerate(requests):
            if r.scope is not None:
                # Scoped responds never ride the fused C stream —
                # per-row lane filtering can't; object path + encode
                # (server/scope.py), ingest already done by the batch.
                from evolu_tpu.server import scope as scope_mod

                resp = scope_mod.scoped_response(self.store, r)
                held[i] = len(resp.messages)
                out.append(protocol.encode_sync_response(resp))
                continue
            tree, raw = self._resolve_tree(r.user_id, trees, tree_strings)
            # A generic store (no `.db` attribute at all) must degrade
            # to the object-respond fallback, not AttributeError.
            db = getattr(shards[shard_ix(r.user_id)], "db", None)
            if db is None or not hasattr(db, "fetch_relay_messages_wire"):
                fallback.append((i, r))
                out.append(None)
                continue
            with diffing:
                since = response_since(tree, merkle_tree_from_string(r.merkle_tree))
            stream = b""
            if since is not None:
                try:
                    with fetching:
                        stream, held[i] = db.fetch_relay_messages_wire(
                            r.user_id, since, r.node_id)
                except NonCanonicalStoreError:
                    # A malformed stored width degrades this request to
                    # the object path (generic SQL), like sync_wire.
                    fallback.append((i, r))
                    out.append(None)
                    continue
            out.append(stream + protocol._string(2, raw))
        if fallback:
            resps = self._respond([r for _i, r in fallback], trees, tree_strings)
            for (i, _r), resp in zip(fallback, resps):
                held[i] = len(resp.messages)
                out[i] = protocol.encode_sync_response(resp)
        with_messages = sum(1 for n in held if n)
        metrics.observe_many(
            (diffing.observation(), fetching.observation()),
            also_inc=(
                ("evolu_engine_respond_requests_total",
                 len(out) - with_messages, {"answer": "empty"}),
                ("evolu_engine_respond_requests_total",
                 with_messages, {"answer": "messages"}),
                ("evolu_engine_respond_messages_total", sum(held), {}),
                ("evolu_engine_respond_bytes_total", sum(map(len, out)), {}),
            ))
        return out


# -- pod-scale multi-process reconcile (VERDICT r3 #3) --
#
# The reference deploys one relay process (apps/server/src/index.ts:
# 224-248); the BASELINE "one pod pass" north star describes the same
# server at pod scale. `reconcile_pod` runs the WHOLE server across a
# jax.distributed cluster: storage is partitioned by a stable owner →
# process hash (an owner's history always lives on one process's
# shards), while the device Merkle leg is ONE SPMD dispatch over the
# GLOBAL mesh — every process participates, feeds only its addressable
# shards, and the XOR digest all-reduce makes the whole-batch digest
# visible pod-wide. Owners are only ever laid out on their OWNING
# process's addressable shards, so each process decodes exactly the
# deltas its stores need — no cross-process delta traffic (the DCN
# carries collectives, not rows).


def owner_process(user_id: str, nproc: int) -> int:
    """Stable owner → process assignment (crc32, like
    ShardedRelayStore.shard_index): storage ownership must survive
    across batches, so it cannot depend on per-batch load."""
    import zlib

    return zlib.crc32(user_id.encode("utf-8")) % nproc


@with_x64
def reconcile_pod(
    mesh: Mesh, store, requests: Sequence[protocol.SyncRequest],
    wire: bool = False,
) -> Tuple[List, int]:
    """One pod pass. Call on EVERY process of the cluster with
    identical `requests` (the ingest fabric broadcasts a batch; each
    process answers for the owners it stores). Returns (responses,
    device_digest): `responses` aligns with `requests`, None for
    requests owned by another process; the digest is the pod-wide XOR
    over every device-hashed row (pre-dedup — the device hashes
    optimistically like `reconcile_stream`), replicated to all
    processes by the all-reduce, so agreement across processes is an
    end-to-end integrity check of the global dispatch.

    With `wire=True`, owned requests get the BYTES-mode response
    (`_respond_wire`: the encoded SyncResponse with its messages stream
    straight from C) — the pod serve path only forwards protobuf, so
    the object layer is skipped; byte-identical to encoding the
    object-mode response (test-pinned).

    Storage semantics per owner are identical to the single-process
    `BatchReconciler.reconcile`: in-batch dedup in request order, PK
    dedup against the store via per-row was-new flags, owners with any
    duplicate row re-folded host-side from their new rows only, one
    atomic insert+tree transaction per storage shard. Single-process
    clusters degenerate to the plain engine semantics exactly (the
    parity test runs both)."""
    from evolu_tpu.core.merkle import minute_deltas_host

    nproc = jax.process_count()
    pid = jax.process_index()
    n_dev = mesh.devices.size

    # 0) In-batch dedup, request order (deterministic on all processes).
    seen: set = set()
    kept: Dict[str, List[protocol.EncryptedCrdtMessage]] = {}
    for r in requests:
        for m in r.messages:
            k = (m.timestamp, r.user_id)
            if k not in seen:
                seen.add(k)
                kept.setdefault(r.user_id, []).append(m)
    owners = list(kept)  # first-appearance order — identical everywhere

    # 1) One vectorized parse; owners with any non-canonical row take
    # the host fold on their owning process (device hash re-renders
    # canonical case — same quarantine rule as deltas_dispatch).
    flat_ts = [m.timestamp for o in owners for m in kept[o]]
    spans: Dict[str, slice] = {}
    pos = 0
    for o in owners:
        spans[o] = slice(pos, pos + len(kept[o]))
        pos += len(kept[o])
    if flat_ts:
        all_m, all_c, all_n, case_ok = parse_timestamp_strings(flat_ts, with_case=True)
    else:
        all_m = all_c = all_n = case_ok = np.zeros(0, np.int64)
    good = [o for o in owners if bool(case_ok[spans[o]].all())]
    good_set = set(good)
    host_only = [o for o in owners if o not in good_set]

    # 2) Global device layout: each owner lands on a shard of its
    # OWNING process (per-process LPT over that process's addressable
    # shard slots) — every process computes the full layout
    # deterministically, then feeds only its addressable slices.
    proc_of = {o: owner_process(o, nproc) for o in good}
    proc_shards: Dict[int, List[int]] = {}
    for i, d in enumerate(mesh.devices.flat):
        proc_shards.setdefault(d.process_index, []).append(i)
    shards_global: List[List[str]] = [[] for _ in range(n_dev)]
    for p, slots in proc_shards.items():
        mine = {o: len(kept[o]) for o in good if proc_of[o] == p}
        for j, owner_list in enumerate(assign_owners_to_shards(mine, len(slots))):
            shards_global[slots[j]] = owner_list
    shard_len = max((sum(len(kept[o]) for o in s) for s in shards_global), default=0)
    shard_size = bucket_size(max(shard_len, 1))
    total = n_dev * shard_size

    good_ix = {o: i for i, o in enumerate(good)}
    millis = np.zeros(total, np.int64)
    counter = np.zeros(total, np.int32)
    node = np.zeros(total, np.uint64)
    valid = np.zeros(total, bool)
    oix = np.zeros(total, np.int64)
    for si, shard in enumerate(shards_global):
        p0 = si * shard_size
        for o in shard:
            sl_src = spans[o]
            n = sl_src.stop - sl_src.start
            sl = slice(p0, p0 + n)
            millis[sl] = all_m[sl_src]
            counter[sl] = all_c[sl_src]
            node[sl] = all_n[sl_src]
            valid[sl] = True
            oix[sl] = good_ix[o]
            p0 += n

    # 3) ONE SPMD dispatch over the global mesh (uniform: `good` is
    # identical on every process, so either all dispatch or none do).
    digest = 0
    by_ix: Dict[int, Dict[str, int]] = {}
    if good:
        shd = sharding(mesh)
        args = [put_sharded(a, shd) for a in (millis, counter, node, valid, oix)]
        owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted, dev_digest = (
            to_host_many(*_compiled_merkle_kernel(mesh)(*args))
        )
        by_ix = decode_owner_minute_deltas(
            owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted
        )
        digest = int(dev_digest)

    # 4) Storage leg — my owners only, like `_land`: the
    # inserts of every live shard in one native call, tree math per
    # shard, upserts + commits in a second, inside the same atomic
    # transaction window (`_store_pass`).
    local = [o for o in good if proc_of[o] == pid]
    local += [o for o in host_only if owner_process(o, nproc) == pid]
    eng = BatchReconciler(store, mesh)  # storage/respond helpers only
    stores, shard_index = eng._shards()
    per_shard: Dict[int, List[str]] = {}
    for o in local:
        per_shard.setdefault(shard_index(o), []).append(o)
    live = sorted(per_shard)
    trees: Dict[str, dict] = {}
    tree_strings: Dict[str, str] = {}
    pod_ins: Dict[str, int] = {}

    def fold_shard(si: int, was_new, stored_tree, upsert) -> None:
        """Fold one shard's owners from their was-new flags:
        `stored_tree(o)` → the owner's tree before this pass,
        `upsert(o, text)` stores the folded one."""
        pos = 0
        for o in per_shard[si]:
            flags = was_new[pos : pos + len(kept[o])]
            pos += len(kept[o])
            pod_ins[o] = pod_ins.get(o, 0) + int(np.asarray(flags).sum())
            if o in good_ix and bool(flags.all()):
                deltas = by_ix.get(good_ix[o], {})
            else:
                # Duplicates or non-canonical: the exact host
                # fold over this owner's NEW rows only.
                deltas, _d = minute_deltas_host(
                    m.timestamp for m, f in zip(kept[o], flags) if bool(f)
                )
            if not deltas:
                continue
            tree = apply_prefix_xors(stored_tree(o), deltas)
            trees[o] = tree
            tree_strings[o] = merkle_tree_to_string(tree)
            upsert(o, tree_strings[o])

    with span("kernel:merkle", "reconcile_pod",
              owners=len(owners), local_owners=len(local),
              n=len(flat_ts), nproc=nproc):
        if live and getattr(store, "packed", False):
            batches = []
            for si in live:
                sh_owners = per_shard[si]
                ts_list = [m.timestamp for o in sh_owners for m in kept[o]]
                contents = [m.content for o in sh_owners for m in kept[o]]
                batches.append((
                    sh_owners, [len(kept[o]) for o in sh_owners],
                    *_pack_rows(ts_list, contents),
                ))
            with eng._store_pass(stores, live, batches) as (
                    was_new_by_shard, stored, tree_rows):
                for si in live:
                    fold_shard(
                        si, was_new_by_shard[si],
                        lambda o: merkle_tree_from_string(stored[o]),
                        lambda o, text, rows=tree_rows[si]: rows.append((o, text)),
                    )
        elif live:  # stdlib backend: per-row changes==1 flags, per-shard calls
            with eng._shard_transactions(stores, live):
                for si in live:
                    db = stores[si].db
                    was_new = np.array([
                        db.run(
                            'INSERT OR IGNORE INTO "message" '
                            '("timestamp", "userId", "content") VALUES (?, ?, ?)',
                            (m.timestamp, o, m.content),
                        ) == 1
                        for o in per_shard[si]
                        for m in kept[o]
                    ], bool)
                    fold_shard(
                        si, was_new, stores[si].get_merkle_tree,
                        lambda o, text, db=db: db.run(
                            'INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") '
                            "VALUES (?, ?)",
                            (o, text),
                        ),
                    )
    eng.close()
    # Ledger, per process: the broadcast batch ingresses HERE only for
    # rows this process stores (my owners); terminals classify from
    # the was-new flags, in-batch-dedup dropped rows fold into
    # store.duplicate via the request totals.
    local_requests = [
        r for r in requests if owner_process(r.user_id, nproc) == pid
    ]
    ledger.count(
        ledger.INGRESS_SYNC,
        sum(len(r.messages) for r in local_requests),
    )
    _ledger_count_pass(local_requests, pod_ins)

    # 5) Respond for MY requests (message-less cold-sync requests route
    # by the same stable owner hash).
    respond = eng._respond_wire if wire else eng._respond
    responses: List = []
    for r in requests:
        if owner_process(r.user_id, nproc) == pid:
            responses.append(respond([r], trees, tree_strings)[0])
        else:
            responses.append(None)
    return responses, digest
