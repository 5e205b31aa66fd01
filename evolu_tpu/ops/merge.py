"""Batched LWW merge planner on device.

Replaces the reference's per-message SQL loop (reference
packages/evolu/src/applyMessages.ts:78-124) with one columnar pass:

    stable sort by (cell, batch order)
      → segmented exclusive prefix-max of HLC keys (running winner)
      → xor mask   (message's hash goes into the Merkle tree)
      → segmented total max (final winner per cell)
      → upsert mask (final winner beats the stored winner)

Semantics are *exactly* the sequential loop's, including its quirks:
the Merkle XOR is gated on "running winner != message timestamp", not
on the __message insert actually inserting, so a re-received
non-winning duplicate XORs again (applyMessages.ts:104-122) — the
running winner is the max of the stored winner and all *earlier batch
messages* for the same cell, in batch order.

HLC keys are (k1, k2) uint64 pairs from `encode.pack_ts_keys` — k1 =
millis<<16|counter, k2 = node — compared lexicographically; (0, 0) is
the "no stored winner" sentinel (see encode.pack_ts_keys docstring).

Everything here is shape-static and jit-compiled once per bucket size;
`plan_batch_device` pads to power-of-two buckets to avoid recompiles
(SURVEY.md §7 "dynamic shapes").
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import operator

from evolu_tpu.core.timestamp import timestamp_from_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.ops import bucket_size, start_host_transfer, to_host_many, with_x64
from evolu_tpu.ops.encode import node_hex_to_u64, pack_ts_key_host
from evolu_tpu.utils.log import span

# np scalar, NOT jnp: a module-level jnp constant would initialize
# the XLA backend at import time, breaking jax.distributed.initialize
# (multi-host join must run before any backend touch).
_PAD_CELL = np.int32(0x7FFFFFFF)


def _lex_max(a1, a2, b1, b2):
    """Elementwise max of (a1,a2) vs (b1,b2) under lexicographic order."""
    a_wins = (a1 > b1) | ((a1 == b1) & (a2 >= b2))
    return jnp.where(a_wins, a1, b1), jnp.where(a_wins, a2, b2)


def _seg_combine(left, right):
    """The segmented lex-max monoid on (flag, k1, k2): the operand
    nearest the scan head wins outright when flagged."""
    lf, l1, l2 = left
    rf, r1, r2 = right
    m1, m2 = _lex_max(l1, l2, r1, r2)
    return lf | rf, jnp.where(rf, r1, m1), jnp.where(rf, r2, m2)


def _segmented_max_scan_reference(flags, k1, k2, reverse: bool = False):
    """Inclusive segmented lexicographic max scan via
    jax.lax.associative_scan — the semantics reference (and the
    fallback for lengths the blocked variant cannot tile).

    flags[i] marks a segment start (segment END when reverse=True).
    `reverse=True` flips, scans forward with the same combine, and
    flips back (that is how jax implements it), which realizes the
    right-to-left recurrence
    `out[i] = x[i] if flags[i] else max(x[i], out[i+1])`.
    """
    _, m1, m2 = jax.lax.associative_scan(_seg_combine, (flags, k1, k2), reverse=reverse)
    return m1, m2


_SCAN_BLOCK = 256
_PALLAS_SCAN_MIN = 1 << 15  # one pallas grid tile; below this, padding waste wins


def _use_pallas_scan(n: int) -> bool:
    """Trace-time routing of the three segmented scans (lex-max here,
    XOR in merkle_ops, sum in crdt_merge): the single-pass Pallas
    kernel on a TPU backend from one grid tile up, the blocked XLA form
    everywhere else (the CPU tests; EVOLU_PALLAS_SCAN=0 pins it on the
    chip). Below one tile there is no choice and nothing is counted;
    from one tile up the route is counted per TRACE
    (`evolu_merge_scan_total{path}` — a jit-cache hit re-runs no
    Python), so the counter says which form each compiled program
    holds. chip_smoke.py fails on any `path="xla"` count."""
    import os

    if n < _PALLAS_SCAN_MIN:
        return False
    pallas = (
        jax.default_backend() == "tpu"
        and os.environ.get("EVOLU_PALLAS_SCAN", "").lower()
        not in ("0", "false", "off")
    )
    metrics.inc("evolu_merge_scan_total", path="pallas" if pallas else "xla")
    return pallas


def _segmented_max_scan(flags, k1, k2, reverse: bool = False):
    """Inclusive segmented lexicographic max scan — blocked two-level
    formulation, ~2.6× faster than `associative_scan` on TPU at N=1M
    (measured 17.9 → 6.8 ms for the planner's two scans; the generic
    lowering materializes log-depth concat/slice passes, this does
    log2(L) unrolled elementwise passes over an (N/L, L) view + one
    tiny cross-block scan + a carry broadcast).

    On TPU with a big-enough batch the single-pass Pallas kernel
    (ops/pallas_scan.py) takes over — one HBM pass with the carry in
    SMEM across the sequential grid, bit-identical
    (tests/test_pallas.py).

    Identical results to `_segmented_max_scan_reference` (property
    pinned in tests/test_ops.py). Production batches are padded to
    power-of-two buckets so L always tiles; other lengths fall back.
    """
    n = flags.shape[0]
    if _use_pallas_scan(n):
        from evolu_tpu.ops.pallas_scan import segmented_max_scan_pallas

        return segmented_max_scan_pallas(flags, k1, k2, reverse=reverse)
    L = min(_SCAN_BLOCK, n)
    if n == 0 or n % L:
        return _segmented_max_scan_reference(flags, k1, k2, reverse)
    if reverse:
        o1, o2 = _segmented_max_scan(flags[::-1], k1[::-1], k2[::-1])
        return o1[::-1], o2[::-1]

    s_f = flags.reshape(-1, L)
    s1 = k1.reshape(-1, L)
    s2 = k2.reshape(-1, L)
    # In-block inclusive scan (Hillis–Steele): combine each row with
    # the row `shift` to its left; out-of-range pads with the monoid
    # identity (flag=False, keys 0).
    shift = 1
    while shift < L:
        pf = jnp.pad(s_f[:, :-shift], ((0, 0), (shift, 0)), constant_values=False)
        p1 = jnp.pad(s1[:, :-shift], ((0, 0), (shift, 0)))
        p2 = jnp.pad(s2[:, :-shift], ((0, 0), (shift, 0)))
        m1, m2 = _lex_max(p1, p2, s1, s2)
        n1 = jnp.where(s_f, s1, m1)
        n2 = jnp.where(s_f, s2, m2)
        s_f = s_f | pf
        s1, s2 = n1, n2
        shift *= 2
    # Cross-block exclusive carry over the block summaries (tiny:
    # N/L elements), then broadcast into rows whose block prefix holds
    # no segment start (final s_f is exactly that mask).
    _, c1, c2 = jax.lax.associative_scan(
        _seg_combine, (s_f[:, -1], s1[:, -1], s2[:, -1])
    )
    zero = jnp.zeros((), k1.dtype)
    e1 = jnp.concatenate([zero[None], c1[:-1]])
    e2 = jnp.concatenate([zero[None], c2[:-1]])
    carried1, carried2 = _lex_max(e1[:, None], e2[:, None], s1, s2)
    o1 = jnp.where(s_f, s1, carried1)
    o2 = jnp.where(s_f, s2, carried2)
    return o1.reshape(n), o2.reshape(n)


def plan_merge_sorted_core(cell_id, k1, k2, ex_k1, ex_k2, extras=(), return_winners=False):
    """The device LWW planner in SORTED order (traceable core).

    Sorts by (cell, batch order) and returns the masks in that sorted
    order together with the permutation `i_s` (original index of each
    sorted row), skipping the restoring sort — downstream device work
    (hashing, minute segments) runs directly on the sorted rows and the
    host unpermutes the two bool masks with one vectorized numpy
    scatter. `extras` are additional per-row arrays carried through the
    sort as payload operands and returned sorted.

    Args (all shape (N,), padding rows use cell_id=_PAD_CELL, keys 0):
      cell_id: int32 interned (table,row,column) id per message.
      k1, k2: uint64 HLC sort keys per message.
      ex_k1, ex_k2: uint64 stored-winner keys for the message's cell
        ((0,0) = no stored winner).

    Returns (xor_sorted, upsert_sorted, i_s, s1, s2, extras_sorted);
    s1/s2 are the sorted HLC keys, from which callers recover the
    sorted timestamp columns without extra payloads: millis = s1 >> 16,
    counter = s1 & 0xFFFF, node = s2.

    TPU notes: one 32-bit-key sort + two segmented scans. No scatters
    and no segment_max/min (XLA lowers those to serialized scatter
    updates on TPU — ~100ms+ per call at N=1M vs ~15ms for a sort),
    and no post-sort gathers (all per-row data rides through the sort
    as payload operands, ~8x cheaper than u64 gathers at N=1M).

    MUST be traced inside an enable_x64(True) scope (like
    segment_xor2_core): the packed merge key is a real i64 — under
    x64-disabled tracing it would silently degrade to int32 and the
    `cell << 24` shift would scramble the plan for any cell_id >= 128.
    Guarded at trace time below.
    """
    n = cell_id.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    if n <= 1 << 24:
        # ONE packed i64 key (cell << 24 | idx), UNSTABLE: the key
        # total-orders (cell, idx) exactly — idx is unique, so this is
        # bit-identical to the stable-by-cell sort — and drops both
        # the stability requirement and the idx payload (recovered
        # from the key's low bits). Measured r4 on v5e: 0.54 ms/1M
        # faster than the r3 stable-i32 formulation (itself 28% faster
        # than the 2-key sort). Cell ids are non-negative (interned,
        # pad = int32 max), so the packed key sorts pads last.
        key = (cell_id.astype(jnp.int64) << jnp.int64(24)) | idx.astype(jnp.int64)
        if key.dtype != jnp.dtype("int64"):  # x64 disabled: would mis-plan
            raise TypeError(
                "plan_merge_sorted_core must be traced under enable_x64(True): "
                f"packed merge key degraded to {key.dtype}"
            )
        sorted_ops = jax.lax.sort(
            (key, k1, k2, ex_k1, ex_k2) + tuple(extras),
            num_keys=1, is_stable=False,
        )
        key_s = sorted_ops[0]
        c = (key_s >> jnp.int64(24)).astype(jnp.int32)
        i_s = (key_s & jnp.int64((1 << 24) - 1)).astype(jnp.int32)
        s1, s2, e1, e2 = sorted_ops[1:5]
        extras_sorted = sorted_ops[5:]
    else:  # > 16M rows: idx no longer fits the key's low bits
        sorted_ops = jax.lax.sort(
            (cell_id, idx, k1, k2, ex_k1, ex_k2) + tuple(extras),
            num_keys=1, is_stable=True,
        )
        c, i_s, s1, s2, e1, e2 = sorted_ops[:6]
        extras_sorted = sorted_ops[6:]

    seg_start = jnp.concatenate([jnp.ones((1,), bool), c[1:] != c[:-1]])

    # Inclusive segmented max m, then exclusive p (running batch winner
    # BEFORE each message), then seed with the stored winner e.
    m1, m2 = _segmented_max_scan(seg_start, s1, s2)
    zero = jnp.zeros((), jnp.uint64)
    p1 = jnp.where(seg_start, zero, jnp.roll(m1, 1))
    p2 = jnp.where(seg_start, zero, jnp.roll(m2, 1))
    r1, r2 = _lex_max(p1, p2, e1, e2)
    xor_sorted = (r1 != s1) | (r2 != s2)

    # Segment-wide max t: m is nondecreasing within a segment, so a
    # backward segmented max over m broadcasts each segment's final m
    # (= its total max) to every row of the segment.
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    t1, t2 = _segmented_max_scan(seg_end, m1, m2, reverse=True)

    # First row achieving the max in batch order: s == t and no earlier
    # batch row reached t (the exclusive batch max p is still < t).
    eligible = (s1 == t1) & (s2 == t2)
    first_eligible = eligible & ~((p1 == t1) & (p2 == t2))
    # Winner strictly beats the stored winner iff lex_max(t, e) != e.
    beats1, beats2 = _lex_max(t1, t2, e1, e2)
    beats = (beats1 != e1) | (beats2 != e2)
    real = c != _PAD_CELL
    upsert_sorted = first_eligible & beats & real
    xor_sorted = xor_sorted & real
    if return_winners:
        # (beats1, beats2) IS lex_max(segment total max, stored winner)
        # — the cell's updated winner; meaningful at seg_end rows. The
        # HBM winner cache scatters these back over its slots.
        return xor_sorted, upsert_sorted, i_s, s1, s2, extras_sorted, (
            beats1, beats2, seg_end, real,
        )
    return xor_sorted, upsert_sorted, i_s, s1, s2, extras_sorted


def winner_flags(k1, k2, ex_k1, ex_k2):
    """Per-row stored-winner relation bits, computed elementwise BEFORE
    the sort: a = e >lex s, b = e ==lex s. ONE copy shared by
    `plan_merge_sorted_flags` and the packed-owner shard kernel."""
    a = (ex_k1 > k1) | ((ex_k1 == k1) & (ex_k2 > k2))
    b = (ex_k1 == k1) & (ex_k2 == k2)
    return a, b


def masks_from_sorted_flags(grp, s1, s2, a_s, b_s, real):
    """The post-sort planner tail shared by `plan_merge_sorted_flags`
    and the packed-owner shard kernel (`parallel.reconcile`): segment
    boundaries from the sorted GROUP key (the sort-key bits above the
    idx/flag fields — cell, or owner|cell), the two segmented max
    scans, and the flag-bit xor/upsert algebra — ONE copy of the
    correctness-critical mask logic, so the two kernels can never
    drift. → (xor_sorted, upsert_sorted), both already masked by
    `real`."""
    seg_start = jnp.concatenate([jnp.ones((1,), bool), grp[1:] != grp[:-1]])
    m1, m2 = _segmented_max_scan(seg_start, s1, s2)
    zero = jnp.zeros((), jnp.uint64)
    p1 = jnp.where(seg_start, zero, jnp.roll(m1, 1))
    p2 = jnp.where(seg_start, zero, jnp.roll(m2, 1))
    p_eq_s = (p1 == s1) & (p2 == s2)
    p_gt_s = (p1 > s1) | ((p1 == s1) & (p2 > s2))
    # lex_max(p, e) == s ⟺ (p==s ∨ e==s) ∧ p≤s ∧ e≤s; xor is its negation.
    xor_sorted = ~((p_eq_s | b_s) & ~p_gt_s & ~a_s)
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    t1, t2 = _segmented_max_scan(seg_end, m1, m2, reverse=True)
    eligible = (s1 == t1) & (s2 == t2)
    first_eligible = eligible & ~((p1 == t1) & (p2 == t2))
    # beats (t >lex e) read only where s == t: there it is ¬(a ∨ b).
    upsert_sorted = first_eligible & ~(a_s | b_s) & real
    return xor_sorted & real, upsert_sorted


def plan_merge_sorted_flags(cell_id, k1, k2, ex_k1, ex_k2, extras=()):
    """`plan_merge_sorted_core` with the stored-winner payloads REPLACED
    by two flag bits riding in the sort key (r5 kernel restructure).

    The insight: the planner never needs the stored winner's VALUE —
    only its relation to each row's own key. Both e-dependent
    expressions reduce to per-row comparisons computable BEFORE the
    sort:

      xor:    lex_max(p, e) == s  ⟺  (p==s ∨ e==s) ∧ p≤s ∧ e≤s
              — e only enters via (e>s) and (e==s);
      upsert: `beats = t >lex e` is only consumed at rows where s == t
              (first_eligible ⟹ eligible ⟹ s == t), where it equals
              s >lex e ⟺ ¬(e>s) ∧ ¬(e==s).

    So a = (e >lex s) and b = (e ==lex s) are computed elementwise on
    the unsorted columns and packed into the key's two lowest bits:
    key = cell<<26 | idx<<2 | b<<1 | a. The key still total-orders by
    (cell, idx) — idx is unique, the flag bits are never reached — so
    the sort order, masks, and every downstream stage are BIT-IDENTICAL
    to the payload form (property-pinned), but the sort carries 2 u64
    payloads instead of 4 (r4 pricing: ~0.75 ms/payload at 1M).

    Capacity: idx needs 24 bits and cell 36 (n ≤ 2^24 — same guard as
    the packed-key form; larger batches fall back to the payload
    core). The winner-cache kernel keeps `plan_merge_sorted_core`: its
    `return_winners` scatter needs the stored-winner VALUES.

    MUST be traced inside an enable_x64(True) scope (guarded below).
    """
    n = cell_id.shape[0]
    if n > 1 << 24:
        return plan_merge_sorted_core(cell_id, k1, k2, ex_k1, ex_k2, extras)
    idx = jnp.arange(n, dtype=jnp.int32)
    a, b = winner_flags(k1, k2, ex_k1, ex_k2)
    key = (
        (cell_id.astype(jnp.int64) << jnp.int64(26))
        | (idx.astype(jnp.int64) << jnp.int64(2))
        | (b.astype(jnp.int64) << jnp.int64(1))
        | a.astype(jnp.int64)
    )
    if key.dtype != jnp.dtype("int64"):  # x64 disabled: would mis-plan
        raise TypeError(
            "plan_merge_sorted_flags must be traced under enable_x64(True): "
            f"packed merge key degraded to {key.dtype}"
        )
    sorted_ops = jax.lax.sort((key, k1, k2) + tuple(extras), num_keys=1, is_stable=False)
    key_s = sorted_ops[0]
    c = (key_s >> jnp.int64(26)).astype(jnp.int32)
    i_s = ((key_s >> jnp.int64(2)) & jnp.int64((1 << 24) - 1)).astype(jnp.int32)
    a_s = (key_s & jnp.int64(1)) != 0
    b_s = (key_s & jnp.int64(2)) != 0
    s1, s2 = sorted_ops[1:3]
    extras_sorted = sorted_ops[3:]
    xor_sorted, upsert_sorted = masks_from_sorted_flags(
        key_s >> jnp.int64(26), s1, s2, a_s, b_s, c != _PAD_CELL
    )
    return xor_sorted, upsert_sorted, i_s, s1, s2, extras_sorted


def unpermute_masks(xor_sorted, upsert_sorted, i_s, block_size: int = 0):
    """Host side: sorted-order masks + permutation → original batch
    order. With `block_size` > 0 the arrays are concatenated per-shard
    blocks whose `i_s` values are shard-local (the shard_map layout);
    each block unpermutes within its own span. Callers on the hot path
    pre-pull device outputs with `to_host_many` (one transfer wave);
    `to_host` below then no-ops on the numpy arrays."""
    from evolu_tpu.ops import to_host

    xor_sorted = to_host(xor_sorted)
    upsert_sorted = to_host(upsert_sorted)
    i_s = to_host(i_s).astype(np.int64)
    if block_size:
        base = (np.arange(len(i_s), dtype=np.int64) // block_size) * block_size
        i_s = i_s + base
    xor_mask = np.empty_like(xor_sorted)
    upsert_mask = np.empty_like(upsert_sorted)
    xor_mask[i_s] = xor_sorted
    upsert_mask[i_s] = upsert_sorted
    return xor_mask, upsert_mask


def plan_merge_core(cell_id, k1, k2, ex_k1, ex_k2, num_segments: int):
    """Original-order planner: `plan_merge_sorted_core` plus a device
    restoring sort. Kept for callers that need device-resident masks in
    batch order; the shard kernels use the sorted variant and let the
    host unpermute (saves a 1M-row sort per batch).

    Returns (xor_mask, upsert_mask) bools in original batch order.
    """
    del num_segments
    xor_sorted, upsert_sorted, i_s, _, _, _ = plan_merge_sorted_core(
        cell_id, k1, k2, ex_k1, ex_k2
    )
    # A bitonic sort beats a 1M-element scatter on TPU.
    _, xor_mask, upsert_mask = jax.lax.sort(
        (i_s, xor_sorted, upsert_sorted), num_keys=1
    )
    return xor_mask, upsert_mask


plan_merge = jax.jit(plan_merge_core, static_argnames=("num_segments",))


class PlannedBatch(tuple):
    """A planner result that unpacks as the usual (xor_mask, upserts,
    deltas) 3-tuple but also carries the positional bool `upsert_mask`,
    so `storage.apply.apply_messages` can hand the mask straight to the
    C++ `apply_planned` instead of rebuilding it from `upserts` with a
    per-message Python pass."""

    def __new__(cls, xor_mask, upserts, deltas, upsert_mask=None):
        self = super().__new__(cls, (xor_mask, upserts, deltas))
        self.upsert_mask = upsert_mask
        return self


def strip_typed_upserts(plan, messages, schema):
    """Typed-cell plan selection (ISSUE 7), ONE copy for every planner
    (host oracle, device full plan, HBM winner cache, hot-owner shard):
    typed cells NEVER take the LWW app-table upsert — their app value
    is the merge-state materialization (`core.crdt_types`), not the
    winning op's raw value. The xor mask and Merkle deltas are
    TIMESTAMP-ONLY and stay untouched: replication and the winner
    cache's MAX(timestamp) slots are type-agnostic by construction.

    Accepts the 2-tuple, 3-tuple, or PlannedBatch plan shapes and
    returns the same shape with typed upserts removed."""
    typed_idx = [
        i for i, m in enumerate(messages) if schema.is_typed(m.table, m.column)
    ]
    if not typed_idx:
        return plan
    metrics.inc("evolu_crdt_upserts_stripped_total", len(typed_idx))

    def keep(m):
        return not schema.is_typed(m.table, m.column)

    if isinstance(plan, PlannedBatch):
        xor_mask, upserts, deltas = plan
        mask = plan.upsert_mask
        if mask is not None:
            mask = np.array(mask, copy=True)
            mask[typed_idx] = False
        return PlannedBatch(
            xor_mask, [m for m in upserts if keep(m)], deltas, mask
        )
    if len(plan) == 3:
        xor_mask, upserts, deltas = plan
        return xor_mask, [m for m in upserts if keep(m)], deltas
    xor_mask, upserts = plan
    return xor_mask, [m for m in upserts if keep(m)]


def select_messages(messages: Sequence[CrdtMessage], mask: np.ndarray) -> List[CrdtMessage]:
    """messages[i] for mask[i], without a per-message Python loop."""
    ix = np.nonzero(mask)[0]
    if len(ix) == 0:
        return []
    if len(ix) == 1:
        return [messages[int(ix[0])]]
    return list(operator.itemgetter(*ix)(messages))


def winner_key_columns(cells, winners: Dict[Tuple[str, str, str], str]):
    """Per-unique-cell stored-winner key columns: → (ex1_u, ex2_u,
    canonical), zeros where a cell has no stored winner. With
    `read_winner_keys` (the same columns read natively) the ONE
    implementation of winner parse/pack/canonical-check — shared by
    `messages_to_columns`, the HBM cache's lazy seeding, and its
    streamed mode, so the canonical-case rule (a golden-parity
    invariant) can never drift between them."""
    from evolu_tpu.ops.host_parse import parse_timestamp_strings

    winner_cids = [i for i, cell in enumerate(cells) if cell in winners]
    return _key_columns(len(cells), winner_cids, parse_timestamp_strings(
        [winners[cells[i]] for i in winner_cids], with_case=True
    ))


def _key_columns(n, winner_cids, parsed):
    """The n cells' key columns from the parsed winners of
    `winner_cids`; canonical where every winner is."""
    w_millis, w_counter, w_node, w_case_ok = parsed
    ex1_u = np.zeros(n, np.uint64)
    ex2_u = np.zeros(n, np.uint64)
    ex1_u[winner_cids] = pack_ts_key_host(w_millis, w_counter)
    ex2_u[winner_cids] = w_node
    return ex1_u, ex2_u, bool(w_case_ok.all())


def read_winner_keys(db, cells, pb=None, ids=None):
    """`winner_key_columns(cells, fetch_existing_winners(db, cells))`,
    read in one native call where the backend has one
    (`fetch_winner_keys`): a probe of the covering index a cell and the
    46-byte winners parsed by `eh_parse_timestamps`, with no Python
    object a cell. Where `pb` is given, `cells` are `pb.cells[ids]` and
    are bound straight from the batch's interned blob; otherwise they
    are encoded from `cells`. A stored winner of another width or
    storage class, or one the parse refuses, sends the read the string
    way, whose result and errors are `winner_key_columns`' own."""
    from evolu_tpu.core.types import TimestampParseError
    from evolu_tpu.ops.host_parse import parse_packed_timestamps
    from evolu_tpu.storage.apply import fetch_existing_winners

    fetch = getattr(db, "fetch_winner_keys", None)
    if fetch is not None and cells:
        parsed = None
        with anatomy.part("winner_read"):
            if pb is not None:
                present, slab = fetch(pb.cell_blob, pb.cell_lens, ids)
            else:
                from evolu_tpu.storage.native import cell_spans

                present, slab = fetch(*cell_spans(cells))
            if not (present == 2).any():
                try:
                    parsed = parse_packed_timestamps(slab, len(slab) // 46, with_case=True)
                except TimestampParseError:
                    pass
        if parsed is not None:
            return _key_columns(len(cells), np.flatnonzero(present), parsed)
    return winner_key_columns(cells, fetch_existing_winners(db, cells))


def messages_to_columns(
    messages: Sequence[CrdtMessage],
    existing_winners: Dict[Tuple[str, str, str], str],
):
    """Host-side columnarization: intern cells, parse timestamps, pack
    keys — fully vectorized (numpy); no per-message Python. A malformed
    timestamp raises TimestampParseError for the whole batch (matching
    the scalar parser's abort-the-transaction behavior).

    Returns numpy arrays (cell_id, k1, k2, ex_k1, ex_k2) plus the parsed
    (millis, counter, node_u64) columns for the Merkle kernel, plus a
    trailing `canonical` bool: False when any message or stored winner
    uses non-canonical hex case — the device kernels order by numeric
    keys and hash a canonical re-render, which matches the reference's
    raw-string order / verbatim-node hash ONLY for canonical strings,
    so such batches must take the host oracle path.
    """
    from evolu_tpu.ops.host_parse import intern_cells, parse_timestamp_strings

    millis, counter, node, case_ok = parse_timestamp_strings(
        [m.timestamp for m in messages], with_case=True
    )
    canonical = bool(case_ok.all())
    cell_ids, cells = intern_cells(
        [m.table for m in messages], [m.row for m in messages],
        [m.column for m in messages],
    )

    # Stored winners per unique cell (parsed as one vectorized batch).
    ex1_u, ex2_u, winners_canonical = winner_key_columns(cells, existing_winners)
    canonical = canonical and winners_canonical
    ex_k1 = ex1_u[cell_ids]
    ex_k2 = ex2_u[cell_ids]

    k1 = pack_ts_key_host(millis, counter)
    k2 = node
    return cell_ids, k1, k2, ex_k1, ex_k2, millis, counter, node, canonical


def pad_columns(arrays, n: int, pad_cell: bool = True):
    """Pad 1-D columns to the power-of-two bucket ≥ n. First array is
    cell_id (padded with _PAD_CELL); the rest pad with 0."""
    size = bucket_size(n)
    out = []
    for j, a in enumerate(arrays):
        pad_val = int(_PAD_CELL) if (j == 0 and pad_cell) else 0
        p = np.full(size - n, pad_val, dtype=a.dtype)
        out.append(np.concatenate([a, p]))
    return out, size


@with_x64
def plan_batch_device(
    messages: Sequence[CrdtMessage],
    existing_winners: Dict[Tuple[str, str, str], str],
):
    """Drop-in replacement for the host `storage.apply.plan_batch` with
    the decision masks computed on device. Same return contract:
    (xor_mask: list[bool], upserts: list[CrdtMessage])."""
    n = len(messages)
    if n == 0:
        return [], []
    with span("kernel:merge", "plan_batch_device", n=n):
        plan = _plan_batch_device_timed(messages, existing_winners)
    if plan is None:
        return _host_fallback(messages, existing_winners, n)
    return plan


def _host_fallback(messages, existing_winners, n, with_deltas=False):
    """Non-canonical hex case in the batch (or its stored winners):
    device numeric order / canonical-render hash would diverge from the
    reference's raw-string semantics, so route to the host oracle —
    loudly, so a throughput collapse (e.g. an adversarial client
    persisting a non-canonical winner into a hot cell) is visible in
    the kernel logs. `with_deltas` keeps plan_batch_device_full's
    3-tuple contract (host fold with verbatim node case)."""
    from evolu_tpu.obs import ledger, metrics
    from evolu_tpu.storage.apply import plan_batch
    from evolu_tpu.utils.log import log

    metrics.inc("evolu_merge_host_fallbacks_total")
    metrics.inc("evolu_merge_host_fallback_messages_total", n)
    # Ledger TALLY stations (outside the flow equations — the batch's
    # flow still terminates through whichever apply route consumes this
    # plan): how many messages were planned by the host oracle, and the
    # canonicality bounce that sent them here.
    ledger.count(ledger.ROUTE_HOST_FALLBACK, n)
    ledger.count(ledger.BOUNCE_NON_CANONICAL, n)
    log("kernel:merge", "non-canonical hex case: host-planner fallback", n=n)
    xor_mask, upserts = plan_batch(messages, existing_winners)
    if not with_deltas:
        return xor_mask, upserts
    from evolu_tpu.core.merkle import minute_deltas_host

    deltas, _ = minute_deltas_host(
        m.timestamp for flag, m in zip(xor_mask, messages) if flag
    )
    return xor_mask, upserts, deltas


def _plan_batch_device_timed(messages, existing_winners):
    from evolu_tpu.ops.scatter_merge import plan_masks_scatter, scatter_table_for

    n = len(messages)
    cell_ids, k1, k2, ex_k1, ex_k2, *rest = messages_to_columns(messages, existing_winners)
    if not rest[-1]:  # canonical flag
        return None
    table_size = scatter_table_for(cell_ids, k1, k2)
    (cell_ids, k1, k2, ex_k1, ex_k2), size = pad_columns([cell_ids, k1, k2, ex_k1, ex_k2], n)
    if table_size is not None:
        metrics.inc("evolu_merge_plan_total", path="scatter")
        xor_mask, upsert_mask = to_host_many(*plan_masks_scatter(
            jnp.asarray(cell_ids), jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(ex_k1), jnp.asarray(ex_k2), table_size=table_size,
        ))
    else:
        metrics.inc("evolu_merge_plan_total", path="sort")
        xor_mask, upsert_mask = to_host_many(*plan_merge(
            jnp.asarray(cell_ids), jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(ex_k1), jnp.asarray(ex_k2), num_segments=size,
        ))
    return xor_mask[:n].tolist(), select_messages(messages, upsert_mask[:n])


@jax.jit
def _plan_full_kernel(cell_id, k1, k2, ex_k1, ex_k2):
    """Masks + per-minute Merkle XOR deltas in ONE dispatch, all in the
    planner's cell-sorted order (timestamp columns recovered from the
    sorted HLC keys; the single-owner minute segmentation runs with
    owner key 0)."""
    from evolu_tpu.ops.encode import timestamp_hashes, unpack_ts_keys
    from evolu_tpu.ops.merkle_ops import owner_minute_segments

    xor_s, upsert_s, i_s, s1, s2, _ = plan_merge_sorted_flags(cell_id, k1, k2, ex_k1, ex_k2)
    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0))
    zero_owner = jnp.zeros((), jnp.int32)
    _, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        zero_owner, millis_s, hashes, xor_s
    )
    return xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid_sorted


@functools.partial(jax.jit, static_argnames=("table_size",))
def _plan_full_kernel_scatter(cell_id, k1, k2, ex_k1, ex_k2, table_size):
    """Sort-free twin of `_plan_full_kernel` (ops/scatter_merge.py):
    the LWW masks come from the dense scatter-argmax plan in ORIGINAL
    batch order (i_s is the identity — `unpermute_masks` degenerates
    to a copy), and the minute segmentation consumes the original-
    order columns directly (its own tile-local grouping sort is
    order-free — every decoder XOR-merges per key). Same 7-output
    contract; host-level results are bit-identical to the sorted
    kernel wherever the router admits a batch (property-pinned)."""
    from evolu_tpu.ops.encode import timestamp_hashes, unpack_ts_keys
    from evolu_tpu.ops.merkle_ops import owner_minute_segments
    from evolu_tpu.ops.scatter_merge import scatter_plan_masks

    xor_m, upsert_m = scatter_plan_masks(cell_id, k1, k2, ex_k1, ex_k2, table_size)
    i_s = jnp.arange(cell_id.shape[0], dtype=jnp.int32)
    millis, counter = unpack_ts_keys(k1)
    hashes = jnp.where(xor_m, timestamp_hashes(millis, counter, k2), jnp.uint32(0))
    zero_owner = jnp.zeros((), jnp.int32)
    _, minute_sorted, seg_end, seg_xor, valid_sorted = owner_minute_segments(
        zero_owner, millis, hashes, xor_m
    )
    return xor_m, upsert_m, i_s, minute_sorted, seg_end, seg_xor, valid_sorted


def plan_packed_streamed(db, pb, millis, counter, node, cells, touched_ids):
    """Packed plan with winners streamed from SQLite for the touched
    cells — ONE copy of the fetch + scatter + kernel-call sequence,
    shared by the winner cache's streaming mode and the no-cache packed
    route (they must stay identical or the cache-on/off paths diverge).
    `cells` are the touched unique cells; `touched_ids` their indices
    into `pb.cells`. None on a non-canonical stored winner (the caller
    materializes to the object path)."""
    ex1_t, ex2_t, canonical = read_winner_keys(db, cells, pb, touched_ids)
    if not canonical:
        return None
    ex1 = np.zeros(len(pb.cells), np.uint64)
    ex2 = np.zeros(len(pb.cells), np.uint64)
    ex1[touched_ids] = ex1_t
    ex2[touched_ids] = ex2_t
    k1 = pack_ts_key_host(millis, counter)
    return plan_packed_device_full(
        pb.cell_id, k1, node, ex1[pb.cell_id], ex2[pb.cell_id], pb.n
    )


def pull_plan_outputs(outs):
    """Pull a plan kernel's outputs to the host in one wave. ONE copy
    of the seams every plan route gives a tiled Receive
    (`obs.anatomy.tiles`; no-ops in any other command): the caller's
    `device_call` tile, opened just before its first `device_put`, ends
    once the copies are started, `pull` is only the blocking wait for
    them, and everything after it up to the worker's commit is `apply`
    (mask unpermute, delta decode, the SQLite apply, the tree fold)."""
    start_host_transfer(*outs)
    anatomy.seam("pull")
    pulled = to_host_many(*outs)
    anatomy.seam("apply")
    return pulled


def _run_full_plan(cell_ids, k1, k2, ex_k1, ex_k2, n: int):
    """ONE copy of the full-plan dispatch sequence (pad →
    `_plan_full_kernel` → one-wave pull → unpermute → delta decode),
    shared by `plan_batch_device_full` and `plan_packed_device_full` —
    the object and packed routes must produce identical plans, so the
    sequence lives here. → (xor_mask, upsert_mask, deltas), masks in
    batch order, length n. Callers hold the x64 scope and have already
    verified the canonical-case invariant."""
    from evolu_tpu.ops.merkle_ops import decode_minute_delta_arrays
    from evolu_tpu.ops.scatter_merge import scatter_table_for

    # Admission + table sizing in one pre-pad pass (pad rows use the
    # dump slot, never the table).
    table_size = scatter_table_for(cell_ids, k1, k2)
    (cell_ids, k1, k2, ex_k1, ex_k2), _size = pad_columns(
        [cell_ids, k1, k2, ex_k1, ex_k2], n
    )
    anatomy.seam("device_call")
    if table_size is not None:
        metrics.inc("evolu_merge_plan_total", path="scatter")
        outs = _plan_full_kernel_scatter(
            jnp.asarray(cell_ids), jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(ex_k1), jnp.asarray(ex_k2), table_size=table_size,
        )
    else:
        metrics.inc("evolu_merge_plan_total", path="sort")
        outs = _plan_full_kernel(
            jnp.asarray(cell_ids), jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(ex_k1), jnp.asarray(ex_k2),
        )
    xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid = pull_plan_outputs(outs)
    xor_mask, upsert_mask = unpermute_masks(xor_s, upsert_s, i_s)
    deltas = decode_minute_delta_arrays(minute_sorted, seg_end, seg_xor, valid)
    return xor_mask[:n], upsert_mask[:n], deltas


@with_x64
def plan_packed_device_full(cell_ids, k1, k2, ex_k1, ex_k2, n: int):
    """Columns-only twin of `plan_batch_device_full` for the fused
    receive path (PackedReceive): same kernel, but the result is
    `(xor_mask, upsert_mask, deltas)` with positional numpy masks
    only — the packed SQLite apply binds straight from the batch
    buffers, so no `upserts` message list is ever built."""
    with span("kernel:merge", "plan_packed_device_full", n=n):
        return _run_full_plan(cell_ids, k1, k2, ex_k1, ex_k2, n)


@with_x64
def plan_batch_device_full(
    messages: Sequence[CrdtMessage],
    existing_winners: Dict[Tuple[str, str, str], str],
    cols=None,
):
    """Like `plan_batch_device` but ALSO returns the per-minute Merkle
    XOR deltas computed on device — `(xor_mask, upserts, deltas)` — so
    the apply path never hashes timestamps in Python (the reference's
    hot loop #4 eliminated host-side). `cols` optionally reuses a
    caller's `messages_to_columns` result."""
    n = len(messages)
    if n == 0:
        return [], [], {}
    with span("kernel:merge", "plan_batch_device_full", n=n):
        cell_ids, k1, k2, ex_k1, ex_k2, *rest = (
            cols if cols is not None else messages_to_columns(messages, existing_winners)
        )
        if not rest[-1]:  # canonical flag
            return _host_fallback(messages, existing_winners, n, with_deltas=True)
        xor_mask, upsert_mask, deltas = _run_full_plan(
            cell_ids, k1, k2, ex_k1, ex_k2, n
        )
        return PlannedBatch(
            xor_mask.tolist(), select_messages(messages, upsert_mask), deltas, upsert_mask
        )
