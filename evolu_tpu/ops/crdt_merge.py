"""Device kernels for the typed CRDT column folds (ISSUE 7).

Host oracle: `core/crdt_types.py` — everything here is pinned
bit-identical to it on property-sampled op logs (tests/test_crdt_types.py).

**PN-counter** — a segmented SUM over cell-grouped ops, the add-monoid
twin of the LWW planner's segmented lex-max: ONE packed i64 sort key
(cell << 24 | idx, same layout and 2^24 bound as
`merge.plan_merge_sorted_core`), then an inclusive segmented sum scan
whose per-segment total lands at the segment-end row and scatters into
a dense per-cell table. The scan uses the same blocked two-level XLA
formulation as `merge._segmented_max_scan` (the recorded cost model:
generic `associative_scan` ~5 ms/scan at 1M) and hands off to the
single-pass Pallas kernel (`pallas_scan.segmented_sum_scan_pallas`,
u32 hi/lo limb carry) on TPU silicon — exact because pos/neg partial
sums are non-negative and bounded by 2^24 ops × 2^31 < 2^55 per cell.

**AW-set** — the order-free membership fold the PR-4 scatter plan
serves WITHOUT the LWW duplicate-screen caveat: `killed[tag] |= 1` per
kill op and `pair_alive[pair] |= alive[add]` per add op are idempotent
OR-folds, so arbitrary duplicates and arbitrary order produce the same
table — no sorted-hash admission screen, no host-side exactness
boundary. Adopted on both backends; on TPU the recorded v5e law still
prices XLA's serialized scatter above a sort for 1M-row batches, which
`benchmarks/crdt_types.py` records honestly.

Everything traces under enable_x64(True) (i64 packed keys / u64 sums)
and pads to power-of-two buckets (no per-batch recompiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from evolu_tpu.ops import bucket_size, to_host_many, with_x64
from evolu_tpu.ops.merge import _PAD_CELL, _SCAN_BLOCK, _use_pallas_scan
from evolu_tpu.utils.log import span


# --- segmented sum scan (the add-monoid twin of _segmented_max_scan) ---


def _seg_sum_combine(left, right):
    """Segmented-sum monoid on (flag, value): the operand nearest the
    scan head wins outright when flagged, else values add."""
    lf, lv = left
    rf, rv = right
    return lf | rf, jnp.where(rf, rv, lv + rv)


def _segmented_sum_scan_reference(flags, vals):
    """Inclusive segmented sum via jax.lax.associative_scan — the
    semantics reference and the fallback for lengths the blocked
    variant cannot tile."""
    _, out = jax.lax.associative_scan(_seg_sum_combine, (flags, vals))
    return out


def segmented_sum_scan(flags, vals):
    """Inclusive segmented sum, blocked two-level formulation (mirrors
    `merge._segmented_max_scan`: log2(L) shifted elementwise passes over
    an (N/L, L) view + one tiny cross-block scan + a carry broadcast).
    `vals` is uint64; flags[i] marks a segment start. On TPU silicon
    with a big-enough batch the single-pass Pallas kernel takes over
    (same routing rule as the lex-max scan)."""
    n = flags.shape[0]
    if _use_pallas_scan(n):
        from evolu_tpu.ops.pallas_scan import segmented_sum_scan_pallas

        return segmented_sum_scan_pallas(flags, vals)
    L = min(_SCAN_BLOCK, n)
    if n == 0 or n % L:
        return _segmented_sum_scan_reference(flags, vals)
    s_f = flags.reshape(-1, L)
    s_v = vals.reshape(-1, L)
    shift = 1
    while shift < L:
        pf = jnp.pad(s_f[:, :-shift], ((0, 0), (shift, 0)), constant_values=False)
        pv = jnp.pad(s_v[:, :-shift], ((0, 0), (shift, 0)))
        s_v = jnp.where(s_f, s_v, pv + s_v)
        s_f = s_f | pf
        shift *= 2
    _, carry = jax.lax.associative_scan(_seg_sum_combine, (s_f[:, -1], s_v[:, -1]))
    zero = jnp.zeros((), vals.dtype)
    excl = jnp.concatenate([zero[None], carry[:-1]])
    out = jnp.where(s_f, s_v, excl[:, None] + s_v)
    return out.reshape(n)


# --- PN-counter: per-cell (pos, neg) sums ---


@functools.partial(jax.jit, static_argnames=("table_size",))
def pn_counter_sums_core(cell_id, delta, table_size):
    """Traceable core: cell-grouped segmented sums of the positive and
    negative delta parts, scattered into a (table_size,) dense pair of
    u64 tables (slot = cell id; pad rows park on the out-of-range dump
    slot). `cell_id` int32 with _PAD_CELL padding, `delta` int64,
    n ≤ 2^24 (the packed-key bound — the host wrapper chunks above it).
    Must trace under enable_x64(True) (guarded like the merge cores)."""
    n = cell_id.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    key = (cell_id.astype(jnp.int64) << jnp.int64(24)) | idx.astype(jnp.int64)
    if key.dtype != jnp.dtype("int64"):  # x64 disabled: would mis-group
        raise TypeError(
            "pn_counter_sums_core must be traced under enable_x64(True): "
            f"packed key degraded to {key.dtype}"
        )
    key_s, d_s = jax.lax.sort((key, delta), num_keys=1, is_stable=False)
    c_s = (key_s >> jnp.int64(24)).astype(jnp.int32)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), c_s[1:] != c_s[:-1]])
    pos = jnp.where(d_s > 0, d_s, 0).astype(jnp.uint64)
    neg = jnp.where(d_s < 0, -d_s, 0).astype(jnp.uint64)
    pos_sum = segmented_sum_scan(seg_start, pos)
    neg_sum = segmented_sum_scan(seg_start, neg)
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    real = c_s != _PAD_CELL
    tgt = jnp.where(seg_end & real, c_s, jnp.int32(table_size))
    pos_t = jnp.zeros(table_size, jnp.uint64).at[tgt].set(pos_sum, mode="drop")
    neg_t = jnp.zeros(table_size, jnp.uint64).at[tgt].set(neg_sum, mode="drop")
    return pos_t, neg_t


@with_x64
def pn_counter_sums(cell_id: np.ndarray, delta: np.ndarray, num_cells: int):
    """Host entry: → (pos, neg) int64 numpy arrays of length num_cells,
    bit-identical to `crdt_types.fold_counter_ops` per cell. Batches
    beyond the 2^24 packed-key bound fold in chunks — the sum monoid is
    associative/commutative, so chunked accumulation is exact."""
    n = len(cell_id)
    if n == 0:
        z = np.zeros(num_cells, np.int64)
        return z, z.copy()
    with span("kernel:crdt", "pn_counter_sums", n=n, cells=num_cells):
        table = bucket_size(max(num_cells, 1))
        pos = np.zeros(table, np.uint64)
        neg = np.zeros(table, np.uint64)
        chunk = 1 << 24
        for i in range(0, n, chunk):
            c = cell_id[i : i + chunk]
            d = delta[i : i + chunk]
            size = bucket_size(len(c))
            c_p = np.concatenate(
                [c.astype(np.int32), np.full(size - len(c), int(_PAD_CELL), np.int32)]
            )
            d_p = np.concatenate([d.astype(np.int64), np.zeros(size - len(d), np.int64)])
            p_t, n_t = to_host_many(*pn_counter_sums_core(
                jnp.asarray(c_p), jnp.asarray(d_p), table_size=table
            ))
            pos += p_t
            neg += n_t
        return pos[:num_cells].astype(np.int64), neg[:num_cells].astype(np.int64)


# --- AW-set: the order-free membership fold ---


@functools.partial(jax.jit, static_argnames=("num_tags",))
def _killed_table_core(kill_ids, num_tags):
    """Idempotent scatter-OR: killed[tag] = any kill op names it. Pad
    rows target the dump slot."""
    return (
        jnp.zeros(num_tags + 1, jnp.int32).at[kill_ids].max(1, mode="drop")[:num_tags]
    )


@functools.partial(jax.jit, static_argnames=("num_pairs",))
def awset_pair_alive_core(pair_id, alive, num_pairs):
    """Per-(cell, elem) membership: pair_alive[p] = OR over its adds'
    alive flags — order-free, duplicate-safe (the scatter shape with no
    LWW caveat). Pad rows use pair_id = num_pairs (dump)."""
    return (
        jnp.zeros(num_pairs + 1, jnp.int32)
        .at[pair_id]
        .max(alive.astype(jnp.int32), mode="drop")[:num_pairs]
    )


def awset_alive_flags(add_tags, kills, state_killed):
    """Device twin of `crdt_types.alive_add_flags`: membership via a
    dense killed-tag table (host interning + one scatter + one gather)
    instead of Python set probes. → list[bool], bit-identical."""
    n = len(add_tags)
    if n == 0:
        return []
    with span("kernel:crdt", "awset_alive_flags", n=n):
        kill_list = [t for t in kills if t is not None]
        kill_list.extend(state_killed)
        universe, inverse = np.unique(
            np.array(list(add_tags) + kill_list, dtype=object), return_inverse=True
        )
        num_tags = len(universe)
        add_ids = inverse[:n].astype(np.int32)
        kill_ids = inverse[n:].astype(np.int32)
        size = bucket_size(max(len(kill_ids), 1), multiple=16)
        kill_p = np.concatenate(
            [kill_ids, np.full(size - len(kill_ids), num_tags, np.int32)]
        )
        killed = np.asarray(_killed_table_core(jnp.asarray(kill_p), num_tags=num_tags))
        return [not bool(killed[i]) for i in add_ids]


def awset_membership(pair_id: np.ndarray, alive: np.ndarray, num_pairs: int):
    """Host entry for the per-(cell, elem) fold: → int32 numpy 0/1 of
    length num_pairs. Used by the bench and the rebuild path; the
    incremental apply stores per-add alive rows and lets SQL DISTINCT
    do the membership."""
    n = len(pair_id)
    if n == 0:
        return np.zeros(num_pairs, np.int32)
    size = bucket_size(n)
    p_p = np.concatenate(
        [pair_id.astype(np.int32), np.full(size - n, num_pairs, np.int32)]
    )
    a_p = np.concatenate([alive.astype(np.int32), np.zeros(size - n, np.int32)])
    out = awset_pair_alive_core(jnp.asarray(p_p), jnp.asarray(a_p), num_pairs=num_pairs)
    return np.asarray(out)


# --- sharded (owner, cell) counter sums — the reconcile-shaped fold ---


def counter_shard_sums_core(owner_ix, cell_id, delta):
    """Per-shard typed fold for the multi-owner reconcile shape
    (`parallel.reconcile`): ops group by the SAME packed owner|cell|idx
    i64 sort key as the LWW shard kernel (`pack_owner_cell_key`,
    lo_bits=0 — the sum monoid needs no stored-winner flag bits), then
    the segmented sums run per (owner, cell) segment. Returns the
    sorted group keys, segment-end mask, and inclusive pos/neg sums —
    the per-cell totals sit at seg-end rows, and every output feeds the
    bench's checksum carry (tests/test_bench_liveness.py discipline).
    Must trace under enable_x64(True); callers wrap in shard_map over
    the owners axis (owners are never split across shards, so local
    segments are globally complete)."""
    from evolu_tpu.parallel.reconcile import pack_owner_cell_key

    n = cell_id.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    key = pack_owner_cell_key(owner_ix, cell_id, idx, lo_bits=0)
    key_s, d_s = jax.lax.sort((key, delta), num_keys=1, is_stable=False)
    grp = key_s >> jnp.int64(24)  # owner|cell bits above idx
    seg_start = jnp.concatenate([jnp.ones((1,), bool), grp[1:] != grp[:-1]])
    pos = jnp.where(d_s > 0, d_s, 0).astype(jnp.uint64)
    neg = jnp.where(d_s < 0, -d_s, 0).astype(jnp.uint64)
    pos_sum = segmented_sum_scan(seg_start, pos)
    neg_sum = segmented_sum_scan(seg_start, neg)
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    return grp, seg_end, pos_sum, neg_sum
