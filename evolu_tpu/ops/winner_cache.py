"""HBM-resident per-cell winner cache (SURVEY.md §7 hard part 4).

The round-1 design streamed each batch's stored winners out of SQLite
(`storage.apply.fetch_existing_winners`) and shipped them to the device
as `ex_k1/ex_k2` columns. This module is the measured alternative the
round-1 review asked for: the per-cell winner table LIVES in device
memory across batches — the kernel gathers stored winners from HBM,
plans the batch, and scatter-updates the winners in place (donated
buffers, so XLA reuses the allocation) — with SQLite as the durable
write-behind it always was. Per steady-state batch this removes the
SQLite winner read, the winner-string parse, and the 16·N-byte ex
column host→device transfer.

Coherence contract:
- SQLite remains the source of truth. Cache slots are seeded lazily:
  the first time a cell is seen, its winner is read from SQLite (one
  batched read for all new cells). After that the kernel's scatter
  keeps the slot exactly equal to SQLite's `MAX(timestamp)` for the
  cell, because every apply goes through `plan_batch` below.
- The scatter runs at plan time, inside the caller's transaction. If
  the transaction fails the cache is ahead of SQLite, so
  `on_transaction_failed()` (hooked by `storage.apply.apply_messages`)
  drops everything — the next batch re-seeds from SQLite. Cheap and
  always safe.
- Non-canonical hex case (messages or stored winners) cannot be
  ordered by numeric keys (reference semantics are raw-string order);
  such batches fall back to the host oracle planner and every touched
  cell is invalidated, mirroring `merge._host_fallback`.
- TYPED cells (CRDT column types, core/crdt_types.py) keep the slot ==
  MAX(timestamp) invariant unchanged — the xor/Merkle algebra the slot
  feeds is timestamp-only and type-agnostic. What differs is the slot's
  MEANING: for an LWW cell the slot's timestamp is also the app-table
  winner; for a typed cell the app value is merge STATE (__crdt_* fold,
  materialized by storage.apply) and the slot is only the xor gate.
  Invalidation per type: LWW invalidation rules apply verbatim; typed
  merge state never lives in HBM (it lives in SQLite inside the apply
  transaction), so typed state reset/rollback needs no extra cache
  hook — the existing transaction-failure reset already covers the
  shared slots. Contract test: tests/test_crdt_types.py pins slot ==
  MAX(timestamp) while the app value is the fold, per type.
- A SECOND connection writing the same database (SyncLock contemplates
  cross-process workers) would silently strand stale winners; every
  `plan_batch` therefore probes `PRAGMA data_version` — which moves
  iff another connection changed the file — and resets the cache when
  it moved. Same-connection applies never move it.

Memory: 16 bytes/cell (two uint64 keys), power-of-two capacity grown by
doubling — 1M cells = 16 MiB of HBM. Invalidated cells release their
slots to a free list; re-assignment always rewrites the slot (winner or
zeros), so a reused slot cannot leak a previous cell's keys.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.ops import bucket_size, to_host_many, with_x64
from evolu_tpu.ops.encode import pack_ts_key_host, timestamp_hashes, unpack_ts_keys
from evolu_tpu.ops.host_parse import intern_cells, parse_timestamp_strings
from evolu_tpu.ops.merge import (
    _PAD_CELL,
    PlannedBatch,
    plan_merge_sorted_core,
    pull_plan_outputs,
    select_messages,
    unpermute_masks,
)
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.ops.merkle_ops import decode_minute_delta_arrays, owner_minute_segments
from evolu_tpu.utils.log import span

Cell = Tuple[str, str, str]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _cached_plan_kernel(w1, w2, slots, cell_id, k1, k2):
    """Gather stored winners from the HBM cache, plan, scatter the
    updated winners back — one dispatch, cache buffers donated (updated
    in place). Padding rows carry slot 0; their gathered value is dead
    (masked by the pad cell) and their scatter target is the
    out-of-range dump index (dropped)."""
    e1 = w1[slots]
    e2 = w2[slots]
    xor_s, upsert_s, i_s, s1, s2, (slots_s,), (win1, win2, seg_end, real) = (
        plan_merge_sorted_core(
            cell_id, k1, k2, e1, e2, extras=(slots,), return_winners=True
        )
    )
    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0))
    zero_owner = jnp.zeros((), jnp.int32)
    _, minute_sorted, m_seg_end, seg_xor, valid_sorted = owner_minute_segments(
        zero_owner, millis_s, hashes, xor_s
    )
    cap = jnp.int32(w1.shape[0])
    tgt = jnp.where(seg_end & real, slots_s, cap)
    w1 = w1.at[tgt].set(win1, mode="drop")
    w2 = w2.at[tgt].set(win2, mode="drop")
    return w1, w2, xor_s, upsert_s, i_s, minute_sorted, m_seg_end, seg_xor, valid_sorted


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _seed_kernel(w1, w2, idx, v1, v2):
    """Write seed winners into cache slots (padding rows target the
    out-of-range dump index and are dropped)."""
    w1 = w1.at[idx].set(v1, mode="drop")
    w2 = w2.at[idx].set(v2, mode="drop")
    return w1, w2


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("new_cap",))
def _grow_kernel(w, new_cap):
    out = jnp.zeros(new_cap, w.dtype)
    return jax.lax.dynamic_update_slice(out, w, (0,))


# -- PR-12 mesh-sharded slot arrays (MeshShardedWinnerCache below) --
#
# Compiled shard_map kernels for the owner-sharded HBM winner store,
# cached per mesh (the mesh object is the jit-cache key, so every
# consumer sharing a MeshContext shares ONE compiled pipeline per
# bucket). Registered for the recompile fence like the engine's
# _JIT_KERNELS (`mesh_jit_cache_size`).

_MESH_JIT_KERNELS: List = []


def mesh_jit_cache_size() -> int:
    """Jit-cache entries across the sharded winner-cache kernels — the
    recompile fence for the sharded pipeline (same private
    `_cache_size` surface as `engine.merkle_jit_cache_size`)."""
    return sum(k._cache_size() for k in _MESH_JIT_KERNELS)


def _sharded_plan_body(w1, w2, slots, cell_id, k1, k2):
    """Per-device gather/plan/scatter — `_cached_plan_kernel`'s body on
    this device's (1, cap) slot rows and (S,) batch slice. Cells are
    placed per shard (stable hash), so cell segments never span
    devices; minute segments are per-shard partials the host decoder
    XOR-merges exactly (the cross-device delta reduction)."""
    w1r, w2r = w1[0], w2[0]
    e1 = w1r[slots]
    e2 = w2r[slots]
    xor_s, upsert_s, i_s, s1, s2, (slots_s,), (win1, win2, seg_end, real) = (
        plan_merge_sorted_core(
            cell_id, k1, k2, e1, e2, extras=(slots,), return_winners=True
        )
    )
    millis_s, counter_s = unpack_ts_keys(s1)
    hashes = jnp.where(xor_s, timestamp_hashes(millis_s, counter_s, s2), jnp.uint32(0))
    zero_owner = jnp.zeros((), jnp.int32)
    _, minute_sorted, m_seg_end, seg_xor, valid_sorted = owner_minute_segments(
        zero_owner, millis_s, hashes, xor_s
    )
    cap = jnp.int32(w1r.shape[0])
    tgt = jnp.where(seg_end & real, slots_s, cap)
    w1r = w1r.at[tgt].set(win1, mode="drop")
    w2r = w2r.at[tgt].set(win2, mode="drop")
    return (
        w1r[None], w2r[None],
        xor_s, upsert_s, i_s, minute_sorted, m_seg_end, seg_xor, valid_sorted,
    )


@functools.lru_cache(maxsize=None)
def _sharded_plan_kernel(mesh):
    from jax import shard_map
    from evolu_tpu.parallel.mesh import OWNERS_AXIS
    from jax.sharding import PartitionSpec as P

    spec2, spec1 = P(OWNERS_AXIS, None), P(OWNERS_AXIS)
    fn = jax.jit(
        shard_map(
            _sharded_plan_body,
            mesh=mesh,
            in_specs=(spec2, spec2, spec1, spec1, spec1, spec1),
            out_specs=(spec2, spec2) + (spec1,) * 7,
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    _MESH_JIT_KERNELS.append(fn)
    return fn


def _sharded_seed_body(w1, w2, idx, v1, v2):
    w1 = w1.at[0, idx[0]].set(v1[0], mode="drop")
    w2 = w2.at[0, idx[0]].set(v2[0], mode="drop")
    return w1, w2


@functools.lru_cache(maxsize=None)
def _sharded_seed_kernel(mesh):
    from jax import shard_map
    from evolu_tpu.parallel.mesh import OWNERS_AXIS
    from jax.sharding import PartitionSpec as P

    spec2 = P(OWNERS_AXIS, None)
    fn = jax.jit(
        shard_map(
            _sharded_seed_body,
            mesh=mesh,
            in_specs=(spec2,) * 5,
            out_specs=(spec2, spec2),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    _MESH_JIT_KERNELS.append(fn)
    return fn


class DeviceWinnerCache:
    """Keeps (k1, k2) winner keys per cell in device memory across
    batches. `plan_batch` matches the planner contract of
    `storage.apply.apply_messages` but advertises
    `fetches_winners = False`: apply skips its SQLite winner read and
    the cache seeds misses itself."""

    fetches_winners = False

    # Adaptive gating (VERDICT r2 #3): when a batch's NEW-cell rate is
    # high, the extra seed dispatch makes the cache LOSE to streaming
    # winners from SQLite (measured: 30.9k cached vs 38.8k streamed
    # msgs/sec under the rotating-cell shape); when the population is
    # steady the cache WINS (~+30%). An EWMA of the per-batch seed
    # rate drives a hysteresis: above `seed_hi` the planner streams
    # (cache dropped, membership tracked host-side only); below
    # `seed_lo` it warms the cache back up. The fresh-decaying EWMA
    # (new weight 0.8) returns to cached mode ~2 clean batches after a
    # churn burst ends (one streamed, one warming); a workload that
    # churns a quarter of its cells every batch holds the EWMA near
    # 0.25 — inside the hysteresis band, so no mode oscillation.
    seed_hi = 0.30
    seed_lo = 0.10
    _EWMA_NEW_WEIGHT = 0.8
    _KNOWN_CAP = 1 << 20  # bound the streaming-mode membership estimator

    def __init__(
        self,
        db,
        capacity: int = 1 << 15,
        adaptive: bool = True,
        max_slots: "int | None" = 1 << 22,
    ):
        self._db = db
        self._slots: Dict[Cell, int] = {}
        self._free: List[int] = []  # invalidated slots, reused first
        self._next_slot = 0
        # HBM bound (VERDICT #3): the cache may never grow past
        # `max_slots` (default 2^22 cells = 64 MiB of winner keys —
        # an unbounded workload writing ever-new cells previously grew
        # it without limit). Overflow evicts by DROP-AND-RESEED:
        # eviction IS invalidation, which the coherence protocol
        # already supports (a dropped slot just re-seeds from SQLite on
        # next touch), so capping can never produce a stale winner.
        self.max_slots = max_slots
        if max_slots is not None:
            capacity = min(capacity, bucket_size(max_slots))
        self.capacity = capacity
        self.adaptive = adaptive  # False = always-cached (static path)
        self._seed_ewma = 0.0
        self._streaming = False
        self._known: set = set()  # membership estimator while streaming
        # The first batch after a reset re-seeds every cell it touches;
        # that 1.0 new-cell rate is recovery, not churn, and must not
        # flip a steady workload into streamed mode (~3 batches of
        # penalty per unrelated rollback otherwise). At most ONE skip
        # per run of resets (_ewma_suppressed): under repeated resets
        # (e.g. a foreign writer touching the DB every batch) the
        # sustained 1.0 rates ARE the workload signal and must reach
        # the EWMA, or the gate starves and never streams.
        self._skip_ewma_once = False
        self._ewma_suppressed = False
        # The cache==MAX(timestamp) invariant assumes this worker's
        # connection observes every apply. SQLite's data_version moves
        # if and only if ANOTHER connection changed the database — the
        # cheap per-batch foreign-write probe. Same-connection writes
        # never move it, so steady-state batches pay one PRAGMA read.
        self._data_version = self._read_data_version()
        self._alloc_slot_arrays()

    # -- overridable array hooks (MeshShardedWinnerCache reshapes the
    # slot store to per-device rows; all coherence/gating logic above
    # these hooks is shared verbatim) --

    def _alloc_slot_arrays(self) -> None:
        with jax.enable_x64(True):
            self._w1 = jnp.zeros(self.capacity, jnp.uint64)
            self._w2 = jnp.zeros(self.capacity, jnp.uint64)

    def _clear_free_slots(self) -> None:
        self._free.clear()
        self._next_slot = 0

    def _gather_slot_values(self, idx: np.ndarray):
        """Device-side gather of the audited slots, pulled in ONE wave
        (never a full-array pull — see verify_against_db)."""
        with jax.enable_x64(True):
            j_idx = jnp.asarray(idx)
            return to_host_many(self._w1[j_idx], self._w2[j_idx])

    def _read_data_version(self):
        try:
            rows = self._db.exec_sql_query("PRAGMA data_version", ())
            return next(iter(rows[0].values())) if rows else None
        except Exception:  # noqa: BLE001 - a backend without PRAGMA
            # support degrades to the documented single-writer contract
            return None

    def _drop_if_foreign_write(self) -> None:
        version = self._read_data_version()
        if version != self._data_version:
            self._data_version = version
            if self._has_slot_state():
                metrics.inc("evolu_winner_cache_foreign_write_drops_total")
                self.reset()

    def _has_slot_state(self) -> bool:
        """Anything live OR freed in the slot store — the foreign-write
        reset gate (a hook: the sharded subclass keeps its free lists
        per shard, and the gate must see them identically)."""
        return bool(self._slots or self._free)

    # -- slot management --

    def _grow_to(self, needed: int) -> None:
        new_cap = self.capacity
        while new_cap < needed:
            new_cap *= 2
        if new_cap != self.capacity:
            with jax.enable_x64(True):
                self._w1 = _grow_kernel(self._w1, new_cap=new_cap)
                self._w2 = _grow_kernel(self._w2, new_cap=new_cap)
            self.capacity = new_cap
            metrics.inc("evolu_winner_cache_grows_total")
            metrics.set_gauge("evolu_winner_cache_capacity_slots", new_cap)

    def _seed_new_cells(self, new_cells: List[Cell], pb=None, ids=None) -> bool:
        """Assign slots to first-seen cells (reusing invalidated slots
        first) and load their winners from SQLite in one batched read
        (`read_winner_keys`; `pb` / `ids`: the cells are `pb.cells[ids]`,
        bound from the batch's blob). Every assigned slot is written —
        winner keys for cells with history, zeros for the rest, so a
        reused slot can never leak a previous cell's stale keys. Returns
        False when any seed winner is non-canonical (the caller must
        take the host path; the non-canonical cells stay unassigned)."""
        from evolu_tpu.ops.merge import read_winner_keys

        n = len(new_cells)
        v1, v2, canonical = read_winner_keys(self._db, new_cells, pb, ids)
        if not canonical:
            # A stored non-canonical winner cannot live in the
            # numeric cache. Keep every cell of this batch
            # uncached; the caller falls back to the host planner.
            metrics.inc("evolu_winner_cache_noncanonical_seeds_total")
            return False
        metrics.inc("evolu_winner_cache_seeded_cells_total", n)
        self._assign_and_write_seeds(new_cells, v1, v2)
        return True

    def _assign_and_write_seeds(self, new_cells, v1, v2) -> None:
        """Slot assignment + the device seed write (the array-shape-
        specific half of `_seed_new_cells`)."""
        n = len(new_cells)
        reused = min(len(self._free), n)
        self._grow_to(self._next_slot + n - reused)
        idx = np.empty(n, np.int32)
        for j, c in enumerate(new_cells):
            if self._free:
                slot = self._free.pop()
            else:
                slot = self._next_slot
                self._next_slot += 1
            idx[j] = self._slots[c] = slot
        (idx_p, v1_p, v2_p), _ = _pad_seed(idx, v1, v2, self.capacity)
        with jax.enable_x64(True):
            self._w1, self._w2 = _seed_kernel(
                self._w1, self._w2, jnp.asarray(idx_p),
                jnp.asarray(v1_p), jnp.asarray(v2_p),
            )

    def _enforce_capacity(self, cells, new_cells):
        """The `max_slots` cap (VERDICT #3), applied between the gate
        and seeding: if this batch's seeds would push the live slot
        count past the cap, evict by DROPPING the whole cache and
        reseeding just this batch's cells — eviction is exactly the
        invalidation the coherence protocol already supports, so a
        capped cache can never serve a stale winner; the cost is one
        re-seed wave for cells that were live. Returns the (possibly
        replaced) new_cells list, or None when this batch ALONE
        exceeds the cap — the caller plans it with SQLite-streamed
        winners (exact, no cache state) instead of thrashing."""
        if self.max_slots is None or not new_cells:
            return new_cells
        if len(self._slots) + len(new_cells) <= self.max_slots:
            return new_cells
        metrics.inc("evolu_winner_cache_evictions_total")
        self.reset()
        if len(cells) > self.max_slots:
            return None
        return list(cells)

    def invalidate(self, cells) -> None:
        dropped = 0
        for c in cells:
            slot = self._slots.pop(c, None)
            if slot is not None:
                self._free.append(slot)
                dropped += 1
        metrics.inc("evolu_winner_cache_invalidated_cells_total", dropped)

    def reset(self) -> None:
        metrics.inc("evolu_winner_cache_resets_total")
        self._slots.clear()
        self._clear_free_slots()
        # Streaming mode sources winners from SQLite and measures churn
        # against the carried-over _known — no 1.0-rate re-seed
        # artifact is possible there, and skipping a genuine churn
        # sample would only delay the streaming exit by a batch. And
        # never skip twice in a row: consecutive resets mean the resets
        # themselves are the workload (see __init__).
        self._skip_ewma_once = not self._streaming and not self._ewma_suppressed
        self._alloc_slot_arrays()

    def on_transaction_failed(self) -> None:
        """The plan-time scatter already advanced the cache; a rolled
        back transaction leaves SQLite behind it, so drop everything
        and re-seed lazily."""
        self.reset()

    # -- the planner --

    def _adaptive_gate(self, cells):
        """ONE copy of the adaptive seeding gate (EWMA + streaming
        hysteresis) shared by `plan_batch` and `plan_packed` — the two
        flows must keep identical cache behavior, so the state machine
        lives here. Updates the EWMA and mode, returns
        (mode, new_cells): "stream" = plan with SQLite-streamed winners
        (cache dropped on entry); "cached" = seed `new_cells` then plan
        from HBM. Every return routes through `_gate_result` (mode
        gauge + streamed-cell counting); cached-mode hit/miss counting
        lives in `_count_cached`, fired by the callers only after
        seeding succeeds — see both docstrings."""
        if not self.adaptive and self._streaming:
            # The gate was disabled while streaming (tests / ops
            # pinning the static path): leave streaming mode so the
            # cached path reseeds from SQLite — keeping `known = _known`
            # here would skip seeding cells whose slots were dropped at
            # the streaming switch (KeyError).
            self._streaming = False
            self._known = set()
        known = self._known if self._streaming else self._slots
        new_cells = [c for c in cells if c not in known]
        rate = len(new_cells) / len(cells)
        if self._skip_ewma_once:
            self._skip_ewma_once = False
            self._ewma_suppressed = True
        else:
            self._seed_ewma = (
                (1 - self._EWMA_NEW_WEIGHT) * self._seed_ewma
                + self._EWMA_NEW_WEIGHT * rate
            )
            self._ewma_suppressed = False
        if not self.adaptive:
            return self._gate_result("cached", cells, new_cells)
        if self._streaming:
            # Bound the membership estimator: sustained churn (the
            # very workload streaming targets) would otherwise grow
            # it forever. On overflow, restart it from this batch —
            # the one-batch rate spike only reinforces streaming.
            if len(self._known) > self._KNOWN_CAP:
                self._known = set(cells)
            else:
                self._known.update(cells)
            if self._seed_ewma > self.seed_lo:
                return self._gate_result("stream", cells, new_cells)
            # Churn subsided: warm the cache back up this batch
            # (known was _known while streaming; recompute vs slots,
            # and release the estimator — cached mode never reads
            # it, and a later burst rebuilds it from _slots).
            self._streaming = False
            self._known = set()
            metrics.inc("evolu_winner_cache_mode_switches_total", to="cached")
            return self._gate_result(
                "cached", cells, [c for c in cells if c not in self._slots]
            )
        if self._seed_ewma > self.seed_hi:
            # Seeding dominates: drop the cache (it stops being
            # maintained, so it must not survive) and stream until
            # the EWMA decays under seed_lo.
            self._streaming = True
            self._known = set(self._slots)
            self._known.update(cells)
            self.reset()  # arms no EWMA skip: _streaming is set
            metrics.inc("evolu_winner_cache_mode_switches_total", to="stream")
            return self._gate_result("stream", cells, new_cells)
        return self._gate_result("cached", cells, new_cells)

    def _gate_result(self, mode, cells, new_cells):
        """Record the gate's mode decision (gauge only). Cell counting
        is DEFERRED to `_count_cached`/`_count_streamed`, fired by the
        callers only once a route is committed — a batch that bounces
        onward (non-canonical stored winner → host fallback or object
        path, which may re-enter this gate) must not be counted twice
        or on the wrong route."""
        metrics.set_gauge("evolu_winner_cache_streaming", 1 if self._streaming else 0)
        return mode, new_cells

    @staticmethod
    def _count_cached(cells, new_cells):
        """Unique cells served from HBM slots (hits) vs seeded from
        SQLite (misses) — counted at the point of no return on the
        cached route (seeding succeeded, the HBM kernel will plan)."""
        metrics.inc("evolu_winner_cache_hits_total", len(cells) - len(new_cells))
        metrics.inc("evolu_winner_cache_misses_total", len(new_cells))

    @staticmethod
    def _count_streamed(cells):
        """Unique cells planned with SQLite-streamed winners — counted
        only once the streamed plan is actually produced."""
        metrics.inc("evolu_winner_cache_streamed_cells_total", len(cells))

    @with_x64
    def plan_batch(self, messages: Sequence[CrdtMessage], existing_winners=None):
        """Planner with the `plan_batch_device_full` contract
        ((xor_mask, upserts, deltas) + positional upsert mask), winners
        sourced from HBM instead of the `existing_winners` argument
        (which apply passes as {} — `fetches_winners = False`)."""
        n = len(messages)
        if n == 0:
            return PlannedBatch([], [], {}, np.zeros(0, bool))
        self._drop_if_foreign_write()
        with span("kernel:merge", "winner_cache.plan_batch", n=n):
            millis, counter, node, case_ok = parse_timestamp_strings(
                [m.timestamp for m in messages], with_case=True
            )
            cell_ids, cells = intern_cells(
                [m.table for m in messages], [m.row for m in messages],
                [m.column for m in messages],
            )
            if not bool(case_ok.all()):
                return self._host_fallback(messages, cells)

            mode, new_cells = self._adaptive_gate(cells)
            if mode == "cached":
                new_cells = self._enforce_capacity(cells, new_cells)
            if mode == "stream" or new_cells is None:
                return self._plan_streamed(
                    messages, cells, cell_ids, millis, counter, node
                )
            if new_cells and not self._seed_new_cells(new_cells):
                return self._host_fallback(messages, cells)
            self._count_cached(cells, new_cells)

            slot_of = np.fromiter(
                (self._slots[c] for c in cells), np.int32, len(cells)
            )
            slots = slot_of[cell_ids]
            xor_mask, upsert_mask, deltas = self._run_cached_plan(
                cell_ids, slots, millis, counter, node, n
            )
            return PlannedBatch(
                xor_mask.tolist(), select_messages(messages, upsert_mask),
                deltas, upsert_mask,
            )

    def _run_cached_plan(self, cell_ids, slots, millis, counter, node, n):
        """ONE copy of the cached kernel-call sequence (pad → gather/
        plan/scatter dispatch → pull → unpermute → delta decode) shared
        by `plan_batch` and `plan_packed` — the two flows must produce
        identical plans, so the sequence lives here. →
        (xor_mask, upsert_mask, deltas), masks in batch order, length n."""
        k1 = pack_ts_key_host(millis, counter)
        size = bucket_size(n)
        pad = size - n
        cell_p = np.concatenate([cell_ids, np.full(pad, int(_PAD_CELL), np.int32)])
        slots_p = np.concatenate([slots, np.zeros(pad, np.int32)])
        k1_p = np.concatenate([k1, np.zeros(pad, np.uint64)])
        k2_p = np.concatenate([node, np.zeros(pad, np.uint64)])

        anatomy.seam("device_call")  # of a tiled Receive; a no-op elsewhere
        self._w1, self._w2, *outs = _cached_plan_kernel(
            self._w1, self._w2, jnp.asarray(slots_p),
            jnp.asarray(cell_p), jnp.asarray(k1_p), jnp.asarray(k2_p),
        )
        xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid = (
            pull_plan_outputs(outs)
        )
        xor_mask, upsert_mask = unpermute_masks(xor_s, upsert_s, i_s)
        deltas = decode_minute_delta_arrays(minute_sorted, seg_end, seg_xor, valid)
        return xor_mask[:n], upsert_mask[:n], deltas

    @with_x64
    def plan_packed(self, pb):
        """Packed twin of `plan_batch` for PackedReceive batches (the
        fused receive leg): columns come straight from the C decrypt —
        timestamps parsed once over the 46-wide slab, cells already
        interned — and the result is positional numpy masks
        `(xor_mask, upsert_mask, deltas)` for the packed SQLite apply,
        so no upsert message list is ever built.

        Returns None when the batch must take the object path instead:
        non-canonical hex case in the batch (checked BEFORE any EWMA /
        cache mutation, so the re-route through `plan_batch` keeps
        adaptive-gate parity with a pure-object flow) or a
        non-canonical stored winner seed (the re-route's own
        `_host_fallback` owns invalidation; `_skip_ewma_once` is armed
        before that bounce so the re-entered gate does not sample the
        EWMA a second time for the same batch)."""
        n = pb.n
        if n == 0:
            return np.zeros(0, bool), np.zeros(0, bool), {}
        self._drop_if_foreign_write()
        with span("kernel:merge", "winner_cache.plan_packed", n=n):
            millis, counter, node, case_ok = pb.parse_timestamps()
            if not bool(case_ok.all()):
                return None
            # A slice shares the full batch's interned cell list; only
            # the ids this chunk touches get slots/seeds.
            touched_ids, cells = pb.touched_cells()

            mode, new_cells = self._adaptive_gate(cells)
            if mode == "cached":
                new_cells = self._enforce_capacity(cells, new_cells)
            if mode == "stream" or new_cells is None:
                return self._plan_packed_streamed(
                    pb, cells, touched_ids, millis, counter, node
                )
            if new_cells and not self._seed_new_cells(
                new_cells, pb, _new_cell_ids(cells, new_cells, touched_ids)
            ):
                # The gate above already took this batch's EWMA sample;
                # the object-path re-route will re-enter the gate (via
                # `plan_batch`) for the SAME batch — arm the one-shot
                # skip so a non-canonical bounce never samples twice.
                self._skip_ewma_once = True
                return None  # non-canonical stored winner → object path
            self._count_cached(cells, new_cells)

            slot_arr = np.zeros(len(pb.cells), np.int32)
            for i in touched_ids:
                slot_arr[int(i)] = self._slots[pb.cells[int(i)]]
            slots = slot_arr[pb.cell_id]
            return self._run_cached_plan(
                pb.cell_id, slots, millis, counter, node, n
            )

    def _plan_packed_streamed(self, pb, cells, touched_ids, millis, counter, node):
        """Streaming-mode packed plan: winners from SQLite, no cache
        state. None on a non-canonical stored winner (object path —
        where the re-entered gate counts the route actually taken, so
        streamed cells count only on a produced plan)."""
        from evolu_tpu.ops.merge import plan_packed_streamed

        plan = plan_packed_streamed(
            self._db, pb, millis, counter, node, cells, touched_ids
        )
        if plan is not None:
            self._count_streamed(cells)
        return plan

    def _plan_streamed(self, messages, cells, cell_ids, millis, counter, node):
        """High-churn mode: winners streamed from SQLite per batch, no
        cache state touched (it was dropped on entry). End state is
        identical to the cached path — both feed the same planner
        kernel; only the winner source differs. The caller's
        already-parsed columns are reused (`cols=`) so the batch is not
        host-parsed a second time — this IS the hot path while churn
        lasts."""
        from evolu_tpu.ops.merge import plan_batch_device_full, read_winner_keys

        ex1_u, ex2_u, canonical = read_winner_keys(self._db, cells)
        if not canonical:
            # Non-canonical stored winner: host oracle (raw-string
            # order / verbatim hashing), same as the cached route.
            return self._host_fallback(messages, cells)
        k1 = pack_ts_key_host(millis, counter)
        self._count_streamed(cells)
        cols = (
            cell_ids, k1, node, ex1_u[cell_ids], ex2_u[cell_ids],
            millis, counter, node, True,
        )
        return plan_batch_device_full(messages, {}, cols=cols)

    # -- the PR-11 invariant audit --

    def verify_against_db(self, sample: "int | None" = None) -> int:
        """Audit the correctness centerpiece of the storage inversion
        (PR-11 / ROADMAP #1, which promotes this cache from cache to
        truth): every LIVE slot's (k1, k2) winner keys must equal
        SQLite's MAX(timestamp) for its cell, read back from the HBM
        slot arrays themselves — not from any host mirror. Streaming
        mode holds no slots (SQLite is the winner source there), so
        the audit is vacuous then by design. → the number of cells
        checked; raises AssertionError naming the first divergent
        cells. `sample` caps the audit to the first N cells (ops
        surface — a full audit of a 2^22-slot cache pulls ~64 MiB off
        the device)."""
        from evolu_tpu.ops.merge import winner_key_columns
        from evolu_tpu.storage.apply import fetch_existing_winners

        cells = list(self._slots)
        if sample is not None:
            cells = cells[: int(sample)]
        if not cells:
            return 0
        winners = fetch_existing_winners(self._db, cells)
        v1, v2, canonical = winner_key_columns(cells, winners)
        if not canonical:
            raise AssertionError(
                "non-canonical stored winner occupies a cache slot "
                "(the host-fallback invalidation contract is broken)"
            )
        # Gather ONLY the audited slots device-side and pull both
        # columns in one wave (CLAUDE.md: never per-array, and a full
        # 2^22-slot pull is the very 64 MiB `sample` exists to avoid).
        idx = np.fromiter((self._slots[c] for c in cells), np.int64, len(cells))
        w1, w2 = self._gather_slot_values(idx)
        bad = []
        for j, c in enumerate(cells):
            if int(w1[j]) != int(v1[j]) or int(w2[j]) != int(v2[j]):
                bad.append((c, int(w1[j]), int(v1[j])))
                if len(bad) >= 5:
                    break
        if bad:
            raise AssertionError(
                f"winner cache != MAX(timestamp) for {len(bad)}+ cells: {bad}"
            )
        return len(cells)

    def _host_fallback(self, messages, cells):
        """Non-canonical hex case: invalidate every touched cell —
        their SQLite winners may now be non-canonical, which the
        numeric cache cannot represent — then delegate to the shared
        host-oracle fallback (raw-string order, verbatim-case hashing;
        one implementation to keep in sync)."""
        from evolu_tpu.ops.merge import _host_fallback
        from evolu_tpu.storage.apply import fetch_existing_winners

        metrics.inc("evolu_winner_cache_host_fallbacks_total")
        self.invalidate(cells)
        existing = fetch_existing_winners(self._db, cells)
        return _host_fallback(messages, existing, len(messages), with_deltas=True)


class MeshShardedWinnerCache(DeviceWinnerCache):
    """PR-12: the winner store SHARDED over the device mesh — slot
    arrays of shape (n_devices, capacity) laid out with a
    `NamedSharding` on the owners axis, cells placed on a STABLE shard
    (crc32 of the cell triple — `parallel.mesh.owner_shard` over the
    interned key, so a cell's slot lives on the same device forever),
    and `plan_batch`/`plan_packed` running ONE shard_map'd
    gather/plan/scatter pass: each device plans the cells it owns from
    its OWN slot rows, and the per-shard (minute, xor) partials are
    XOR-merged by the host decoder exactly (the cross-device reduction
    of per-owner Merkle deltas — decoders merge repeated keys by
    construction).

    Coherence is the base contract, now PER SHARD: every live slot on
    device d equals SQLite's MAX(timestamp) for its cell
    (`verify_against_db` audits through the sharded gather; the
    invalidation/reset/foreign-write hooks are inherited verbatim —
    they operate above the array hooks). Encoded slot ids are
    `local * n_shards + shard`, so growing the per-shard capacity
    (doubling along axis 1) never rewrites an assigned id.
    """

    def __init__(
        self,
        db,
        mesh_ctx=None,
        capacity: int = 1 << 12,
        adaptive: bool = True,
        max_slots: "int | None" = 1 << 22,
    ):
        from evolu_tpu.parallel.mesh import MeshContext

        self.ctx = mesh_ctx if mesh_ctx is not None else MeshContext()
        self.n_shards = self.ctx.n_shards
        self._free_by_shard: List[List[int]] = [[] for _ in range(self.n_shards)]
        self._next_by_shard: List[int] = [0] * self.n_shards
        super().__init__(db, capacity=capacity, adaptive=adaptive,
                         max_slots=max_slots)

    # -- placement --

    def _cell_shard(self, cell: Cell) -> int:
        from evolu_tpu.parallel.mesh import owner_shard

        return owner_shard("\x00".join(cell), self.n_shards)

    def shard_slot_counts(self) -> List[int]:
        """Live slots per device (ops/stats surface; the per-shard
        audit in tests groups its assertions by this placement)."""
        counts = [0] * self.n_shards
        for slot in self._slots.values():
            counts[slot % self.n_shards] += 1
        return counts

    # -- array hooks --

    def _sharding2(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from evolu_tpu.parallel.mesh import OWNERS_AXIS

        return NamedSharding(self.ctx.mesh, P(OWNERS_AXIS, None))

    def _sharding1(self):
        from evolu_tpu.parallel.mesh import sharding

        return sharding(self.ctx.mesh)

    def _alloc_slot_arrays(self) -> None:
        shd = self._sharding2()
        with jax.enable_x64(True):
            self._w1 = jax.device_put(
                jnp.zeros((self.n_shards, self.capacity), jnp.uint64), shd
            )
            self._w2 = jax.device_put(
                jnp.zeros((self.n_shards, self.capacity), jnp.uint64), shd
            )

    def _clear_free_slots(self) -> None:
        self._free = []
        self._next_slot = 0
        self._free_by_shard = [[] for _ in range(self.n_shards)]
        self._next_by_shard = [0] * self.n_shards

    def _has_slot_state(self) -> bool:
        return bool(self._slots) or any(self._free_by_shard)

    def _grow_to(self, needed: int) -> None:
        """Grow the PER-SHARD capacity (axis 1); eager lax is fine —
        growth is doubling-rare and never on the steady-state path."""
        new_cap = self.capacity
        while new_cap < needed:
            new_cap *= 2
        if new_cap == self.capacity:
            return
        shd = self._sharding2()
        with jax.enable_x64(True):
            for name in ("_w1", "_w2"):
                grown = jax.lax.dynamic_update_slice(
                    jnp.zeros((self.n_shards, new_cap), jnp.uint64),
                    getattr(self, name), (0, 0),
                )
                setattr(self, name, jax.device_put(grown, shd))
        self.capacity = new_cap
        metrics.inc("evolu_winner_cache_grows_total")
        metrics.set_gauge("evolu_winner_cache_capacity_slots",
                          self.n_shards * new_cap)

    def _gather_slot_values(self, idx: np.ndarray):
        shard = idx % self.n_shards
        local = idx // self.n_shards
        with jax.enable_x64(True):
            return to_host_many(
                self._w1[jnp.asarray(shard), jnp.asarray(local)],
                self._w2[jnp.asarray(shard), jnp.asarray(local)],
            )

    def _assign_and_write_seeds(self, new_cells, v1, v2) -> None:
        ns = self.n_shards
        by_shard: List[List[int]] = [[] for _ in range(ns)]
        for j, c in enumerate(new_cells):
            by_shard[self._cell_shard(c)].append(j)
        need = self.capacity
        for si, js in enumerate(by_shard):
            fresh = max(len(js) - len(self._free_by_shard[si]), 0)
            need = max(need, self._next_by_shard[si] + fresh)
        self._grow_to(need)
        width = bucket_size(max(max(map(len, by_shard)), 1), multiple=16)
        # Pad rows target the out-of-range local index (dropped).
        idx = np.full((ns, width), self.capacity, np.int32)
        v1_p = np.zeros((ns, width), np.uint64)
        v2_p = np.zeros((ns, width), np.uint64)
        for si, js in enumerate(by_shard):
            for k, j in enumerate(js):
                if self._free_by_shard[si]:
                    local = self._free_by_shard[si].pop()
                else:
                    local = self._next_by_shard[si]
                    self._next_by_shard[si] += 1
                self._slots[new_cells[j]] = local * ns + si
                idx[si, k] = local
                v1_p[si, k] = v1[j]
                v2_p[si, k] = v2[j]
        shd = self._sharding2()
        with jax.enable_x64(True):
            self._w1, self._w2 = _sharded_seed_kernel(self.ctx.mesh)(
                self._w1, self._w2,
                jax.device_put(idx, shd),
                jax.device_put(v1_p, shd),
                jax.device_put(v2_p, shd),
            )

    def invalidate(self, cells) -> None:
        dropped = 0
        for c in cells:
            slot = self._slots.pop(c, None)
            if slot is not None:
                self._free_by_shard[slot % self.n_shards].append(
                    slot // self.n_shards
                )
                dropped += 1
        metrics.inc("evolu_winner_cache_invalidated_cells_total", dropped)

    # -- the sharded plan pass --

    def _run_cached_plan(self, cell_ids, slots, millis, counter, node, n):
        """ONE shard_map dispatch: route each row to the device owning
        its cell's slot (stable placement ⇒ same-cell rows co-locate,
        and within a shard the stable routing keeps them in batch
        order — the planner's idx tiebreak contract), pad per-device
        slices to a common power-of-two bucket, plan on-device, then
        unpermute per shard block and map back through the routing.
        Deltas XOR-merge across the per-shard partials in the decoder
        (cross-device reduction). Masks return in batch order, length
        n — identical results to the base single-device pass
        (parity-pinned in tests/test_mesh_engine.py)."""
        k1 = pack_ts_key_host(millis, counter)
        ns = self.n_shards
        shard = (slots % ns).astype(np.int64)
        local = (slots // ns).astype(np.int32)
        counts = np.bincount(shard, minlength=ns)
        size = bucket_size(max(int(counts.max(initial=0)), 1))
        total = ns * size
        cell_p = np.full(total, int(_PAD_CELL), np.int32)
        slots_p = np.zeros(total, np.int32)
        k1_p = np.zeros(total, np.uint64)
        k2_p = np.zeros(total, np.uint64)
        order = np.argsort(shard, kind="stable")
        offs = np.zeros(ns + 1, np.int64)
        offs[1:] = np.cumsum(counts)
        pos_in_shard = np.empty(n, np.int64)
        pos_in_shard[order] = np.arange(n, dtype=np.int64) - offs[shard[order]]
        dest = shard * size + pos_in_shard
        cell_p[dest] = cell_ids
        slots_p[dest] = local
        k1_p[dest] = k1
        k2_p[dest] = node
        self.ctx.record_occupancy(counts.tolist(), size)
        self.ctx.record_xdev_reduce("winner_minute_partials")
        shd1 = self._sharding1()
        anatomy.seam("device_call")
        self._w1, self._w2, *outs = _sharded_plan_kernel(self.ctx.mesh)(
            self._w1, self._w2,
            jax.device_put(slots_p, shd1), jax.device_put(cell_p, shd1),
            jax.device_put(k1_p, shd1), jax.device_put(k2_p, shd1),
        )
        xor_s, upsert_s, i_s, minute_sorted, seg_end, seg_xor, valid = (
            pull_plan_outputs(outs)
        )
        xor_flat, upsert_flat = unpermute_masks(
            xor_s, upsert_s, i_s, block_size=size
        )
        deltas = decode_minute_delta_arrays(minute_sorted, seg_end, seg_xor, valid)
        return xor_flat[dest], upsert_flat[dest], deltas


def _new_cell_ids(cells, new_cells, touched_ids):
    """The batch ids of `new_cells`, which the gate and
    `_enforce_capacity` leave an ordered subset of `cells` (whose ids
    are `touched_ids`)."""
    if len(new_cells) == len(cells):
        return touched_ids
    new = set(new_cells)
    return touched_ids[np.fromiter((c in new for c in cells), bool, len(cells))]


def _pad_seed(idx, k1, k2, capacity: int):
    """Pad seed columns to a power-of-two bucket; pad rows target the
    out-of-range dump index (dropped by the scatter)."""
    size = bucket_size(len(idx), multiple=16)
    pad = size - len(idx)
    return (
        np.concatenate([idx, np.full(pad, capacity, np.int32)]),
        np.concatenate([k1, np.zeros(pad, np.uint64)]),
        np.concatenate([k2, np.zeros(pad, np.uint64)]),
    ), size
