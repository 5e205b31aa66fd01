"""Batched Merkle-trie updates on device.

The reference inserts timestamps into the trie one at a time, XORing
the murmur hash into every node on the root→minute path (reference
packages/evolu/src/merkleTree.ts:31-50). XOR is associative and
commutative, so a whole batch reduces to **one XOR delta per distinct
minute**; the host then applies each delta along its ≤16-node path
(`core.merkle.apply_prefix_xors`), touching O(distinct-minutes × 16)
nodes instead of O(batch × 16).

Device pass: hash timestamps (fully on device, `encode.timestamp_hashes`)
→ minute key with JS `|0` int32 truncation (merkleTree.ts:39) → sort by
minute → ONE inclusive segmented XOR scan (blocked two-level on CPU,
single-pass Pallas on TPU); at each segment's last row the scan value
IS the segment's XOR total, the only positions decoders read.

Hashes are uint32 on device; the host converts to JS signed int32 when
writing trie nodes.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from evolu_tpu.core.merkle import MinuteDeltas, minutes_base3
from evolu_tpu.core.murmur import to_int32
from evolu_tpu.obs import anatomy
from evolu_tpu.ops import to_host, with_x64
from evolu_tpu.ops.encode import timestamp_hashes


_SENTINEL_HI = 0x7FFFFFFF  # int32 max: masked rows sort after every real key

_XOR_BLOCK = 256


def _seg_xor_combine(left, right):
    """Segmented XOR monoid on (flag, value): the operand nearest the
    scan head wins its prefix outright when flagged."""
    lf, lv = left
    rf, rv = right
    return lf | rf, jnp.where(rf, rv, lv ^ rv)


def segmented_xor_scan_reference(flags, values_u32):
    """Inclusive segmented XOR scan via associative_scan — the
    semantics reference (and the fallback for non-tiling lengths)."""
    _, out = jax.lax.associative_scan(_seg_xor_combine, (flags, values_u32))
    return out


def segmented_xor_scan(flags, values_u32):
    """Inclusive segmented XOR scan, blocked two-level formulation
    (same shape trick as `merge._segmented_max_scan`; the generic
    associative_scan lowering materializes log-depth concat/slice
    passes). On TPU at >=1 pallas tile the single-pass Pallas kernel
    takes over. Bit-identical to the reference (tests/test_ops.py,
    tests/test_pallas.py)."""
    from evolu_tpu.ops import merge

    n = flags.shape[0]
    # Pallas first: it pads internally, so it also covers non-tiling
    # lengths that would otherwise fall back to the slow generic
    # associative_scan (merge._segmented_max_scan orders it the same
    # way for the same reason).
    if merge._use_pallas_scan(n):
        from evolu_tpu.ops.pallas_scan import segmented_xor_scan_pallas

        return segmented_xor_scan_pallas(flags, values_u32)
    L = min(_XOR_BLOCK, n)
    if n == 0 or n % L:
        return segmented_xor_scan_reference(flags, values_u32)
    s_f = flags.reshape(-1, L)
    s = values_u32.reshape(-1, L)
    shift = 1
    while shift < L:
        pf = jnp.pad(s_f[:, :-shift], ((0, 0), (shift, 0)), constant_values=False)
        pv = jnp.pad(s[:, :-shift], ((0, 0), (shift, 0)))
        s = jnp.where(s_f, s, pv ^ s)
        s_f = s_f | pf
        shift *= 2
    _, c = jax.lax.associative_scan(_seg_xor_combine, (s_f[:, -1], s[:, -1]))
    e = jnp.concatenate([jnp.zeros((1,), s.dtype), c[:-1]])
    out = jnp.where(s_f, s, e[:, None] ^ s)
    return out.reshape(n)


# Tile width for the block-local grouping sort. Measured on v5e at
# N=1M: full 1M packed-i64 sort 1.33 ms; row-wise sort of a
# (N/8192, 8192) view 0.24 ms (5.5×; 16384 → 0.76, 65536 → 1.02 —
# smaller tiles win, bounded below by per-tile segment inflation).
_GROUP_TILE = 8192


def segment_xor2_core(hi_i32, lo_i32, hashes_u32, valid=None, tile_local=True):
    """Sorted segmented-XOR reduce over an (hi, lo) int32 key pair
    (traceable core).

    Sort rows grouped by (hi, lo) as ONE packed int64 key — REQUIRES
    the x64 context (every production caller is with_x64-wrapped;
    under enable_x64(False) the << 32 would silently corrupt keys) —
    carrying the hash as the only payload (no post-sort gathers). Per distinct key pair,
    XOR the hashes of its rows via ONE segmented XOR scan (the r3
    rewrite: the previous prefix-xor + running-max + 1M-row-gather
    formulation cost ~10 ms/1M — two generic associative_scan
    lowerings plus a gather TPUs serialize). Masked rows must carry
    hash 0 and hi = _SENTINEL_HI; validity is recovered from the
    sorted hi key itself rather than riding the sort as a payload.
    Returns (hi_sorted, lo_sorted, seg_end, seg_xor, valid_sorted),
    all (N,); rows where seg_end & valid give one (key, xor) per
    distinct key — seg_xor is the INCLUSIVE segmented scan, so it
    equals the segment total exactly at those rows (the only positions
    decoders read).

    GROUPING IS TILE-LOCAL when the length tiles (r4): only grouping —
    never order — matters to the decoders, which XOR-merge repeated
    keys exactly (the hot-owner row split already relies on it), so
    the sort runs row-wise over a (N/8192, 8192) view (5.5× the full
    sort on v5e; XLA sorts each row in VMEM). A key spanning tiles
    emits one partial delta per tile; equal keys meeting at a tile
    junction fuse back into one segment (the boundary test below is
    purely key-equality on the flat view). The only cost is more
    seg_end rows for the host decoders — bounded by what N distinct
    minutes could already produce legitimately — and earlier
    compaction-cap overflows in the engine's compact transfer path
    (which falls back to the full pull, engine.deltas_finish).
    `tile_local=False` keeps the r3 global sort — the compact transfer
    kernel needs it, because its cap headroom is budgeted against
    DISTINCT keys, and tile partials would multiply seg_count by up to
    shard_size/8192, flipping realistic workloads into the full-pull
    fallback (every row pulled to the host and decoded there)."""
    del valid  # masked rows are identified by the hi sentinel
    # ONE packed int64 key, UNSTABLE: only the GROUPING of equal
    # (hi, lo) pairs matters, so the cheapest total order wins —
    # measured 1.95 (2×i32 keys, stable default) → 1.29 ms/1M on v5e,
    # → 0.24 ms tile-local. The original keys unpack from the sorted
    # key's halves.
    key = (hi_i32.astype(jnp.int64) << jnp.int64(32)) | lo_i32.astype(
        jnp.uint32
    ).astype(jnp.int64)
    n = key.shape[0]
    if tile_local and n >= 2 * _GROUP_TILE and n % _GROUP_TILE == 0:
        k2, h2 = jax.lax.sort(
            (key.reshape(-1, _GROUP_TILE), hashes_u32.reshape(-1, _GROUP_TILE)),
            dimension=1, num_keys=1, is_stable=False,
        )
        k_s, h_sorted = k2.reshape(n), h2.reshape(n)
    else:
        k_s, h_sorted = jax.lax.sort((key, hashes_u32), num_keys=1, is_stable=False)
    hi_s = (k_s >> jnp.int64(32)).astype(jnp.int32)
    lo_s = k_s.astype(jnp.int32)  # low 32 bits, int32 wrap = original lo
    valid_sorted = hi_s != jnp.int32(_SENTINEL_HI)
    key_change = k_s[1:] != k_s[:-1]
    seg_start = jnp.concatenate([jnp.ones((1,), bool), key_change])
    seg_end = jnp.concatenate([key_change, jnp.ones((1,), bool)])
    seg_xor = segmented_xor_scan(seg_start, h_sorted)
    return hi_s, lo_s, seg_end, seg_xor, valid_sorted


def js_minutes(millis):
    """JS `((millis/1000/60) | 0)` — float-divide then truncate to int32.
    millis >= 0 so floor == trunc; int32 cast wraps like `|0`.

    r5: the shared u32 hi/lo divmod chain replaces the emulated 64-bit
    division (0.39 ms/1M measured in-pipeline); out-of-range batches
    (pre-1970 / beyond 2106-02-07) keep the exact i64 path.
    Bit-identical either way (property-pinned incl. the boundary in
    tests/test_ops.py)."""
    from evolu_tpu.ops.encode import millis_range_cond, u32_divmod_hi_lo

    def fast(m):
        minute, _r = u32_divmod_hi_lo(m, 60000)
        return minute.astype(jnp.int32)

    def slow(m):
        return (m // 60000).astype(jnp.int32)

    return millis_range_cond(millis, fast, slow)


def owner_minute_segments(owner_ix, millis, hashes_u32, valid, tile_local=True):
    """Segmented XOR over (owner, minute) — owner in the hi half
    (sentinel int32-max for masked rows), JS-wrapped minute in the lo
    half of one packed int64 sort key (x64 context required; measured
    faster than 2×i32 keys on v5e). Shared by the client reconcile
    kernel and the server Merkle kernel (the latter's compact variant
    passes tile_local=False — see segment_xor2_core).

    Returns (owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted).
    """
    hi = jnp.where(valid, owner_ix.astype(jnp.int32), jnp.int32(_SENTINEL_HI))
    lo = jnp.where(valid, js_minutes(millis), jnp.int32(0))
    return segment_xor2_core(hi, lo, hashes_u32, valid, tile_local=tile_local)


def decode_owner_minute_deltas(
    owner_sorted, minute_sorted, seg_end, seg_xor, valid_sorted
) -> Dict[int, Dict[str, int]]:
    """Host side: `owner_minute_segments` outputs → {owner_ix:
    {base3-minute-key: signed-int32 delta}} consumable by
    `core.merkle.apply_prefix_xors`.

    Repeated (owner, minute) keys XOR-combine: the owner-fleet layout
    never splits an owner so keys are unique there, but the hot-owner
    cell sharding produces one partial delta per shard per minute and
    relies on the XOR merge being exact (associative/commutative)."""
    with anatomy.part("tree_fold"):  # of a tiled Receive; a no-op elsewhere
        owner_sorted = to_host(owner_sorted)
        minute_sorted = to_host(minute_sorted)
        ends = to_host(seg_end) & to_host(valid_sorted)
        xs = to_host(seg_xor)
        out: Dict[int, Dict[str, int]] = {}
        for i in np.nonzero(ends)[0]:
            o_ix, minute = int(owner_sorted[i]), int(minute_sorted[i])
            key = minutes_base3(minute * 60000)
            d = out.setdefault(o_ix, {})
            d[key] = to_int32(d.get(key, 0) ^ int(xs[i]))
    return out


def decode_minute_delta_arrays(
    minute_sorted, seg_end, seg_xor, valid_sorted
) -> MinuteDeltas:
    """Host side of a ONE-owner plan (the client's): the same segments
    as `decode_owner_minute_deltas` reads, to sorted distinct minutes
    and their int32 deltas for `core.merkle.fold_minute_deltas`, in
    numpy alone: no key string and no Python step a minute. Tile-local
    grouping hands a minute that spans tiles over once a tile, so the
    partials are sorted by minute and XOR-combined (exact, as there)."""
    with anatomy.part("tree_fold"):  # of a tiled Receive; a no-op elsewhere
        ends = to_host(seg_end) & to_host(valid_sorted)
        minutes = to_host(minute_sorted)[ends].astype(np.int64)
        xs = to_host(seg_xor)[ends].astype(np.uint32, copy=False)
        if len(minutes) > 1 and not bool((minutes[1:] > minutes[:-1]).all()):
            order = np.argsort(minutes, kind="stable")
            minutes, xs = minutes[order], xs[order]
            first = np.flatnonzero(
                np.concatenate(([True], minutes[1:] != minutes[:-1]))
            )
            minutes, xs = minutes[first], np.bitwise_xor.reduceat(xs, first)
        return MinuteDeltas(minutes, xs.view(np.int32))


def minute_deltas_core(millis, counter, node, xor_mask):
    """Per-minute XOR deltas for a timestamp batch (traceable core).

    Args (shape (N,)): millis int64, counter int32, node uint64,
      xor_mask bool (False rows contribute nothing — padding or
      messages whose hash the merge planner excluded).

    Masked rows park under the hi-key sentinel so they sort after (and
    never share a segment with) any real (wrapped) minute.
    """
    hashes = jnp.where(xor_mask, timestamp_hashes(millis, counter, node), jnp.uint32(0))
    hi = jnp.where(xor_mask, jnp.int32(0), jnp.int32(_SENTINEL_HI))
    lo = jnp.where(xor_mask, js_minutes(millis), jnp.int32(0))
    _, lo_s, seg_end, seg_xor, valid_sorted = segment_xor2_core(hi, lo, hashes, xor_mask)
    return lo_s.astype(jnp.int64), seg_end, seg_xor, valid_sorted


merkle_minute_deltas = with_x64(jax.jit(minute_deltas_core))


def minute_deltas_to_dict(m_sorted, seg_end, seg_xor, valid_sorted) -> Dict[str, int]:
    """Host side: device outputs → {base3-minute-key: signed-int32 delta}
    consumable by `core.merkle.apply_prefix_xors`. Repeated minute keys
    XOR-combine — tile-local grouping (segment_xor2_core) emits one
    partial per tile for a minute spanning tiles, and the XOR merge is
    exact (same contract as decode_owner_minute_deltas)."""
    m = np.asarray(m_sorted)
    ends = np.asarray(seg_end)
    xs = np.asarray(seg_xor)
    valid = np.asarray(valid_sorted)
    out: Dict[str, int] = {}
    for i in np.nonzero(ends)[0]:
        if not valid[i]:
            continue  # the sentinel segment (masked rows)
        minute = int(m[i])
        key = minutes_base3(minute * 60000)
        out[key] = to_int32(out.get(key, 0) ^ int(xs[i]))
    return out
