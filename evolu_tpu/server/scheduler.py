"""Continuous-batching sync scheduler: fuse live relay traffic into
single engine passes.

The reference relay services each sync request individually
(apps/server/src/index.ts:148-159), and so did our HTTP relay — one
`RelayStore.sync_wire` store pass per handler thread. The offline
`BatchReconciler` already reconciles a whole batch of SyncRequests in
one fused pass (bulk SQL set-diff + one sharded device Merkle
dispatch), but nothing fed it live traffic. This module is the
admission/dispatch layer between the two: handler threads enqueue
decoded `SyncRequest`s onto a bounded queue and block on per-request
futures; a dispatcher thread closes a micro-batch on whichever comes
first of max-batch-size / max-wait-deadline and runs ONE engine pass
(`BatchReconciler.start_batch`/`finish_batch` on packed stores) whose
wire responses resolve the futures.

Why coalescing is sound (Merkle-CRDTs, arXiv 2004.00107): anti-entropy
is pure set reconciliation — a response depends only on store state
plus that one request, and owners are independent, so a batch of
DISTINCT-owner requests served in one pass is byte-identical to any
sequential order of the same requests. Same-owner requests are NOT
independent (the second's response must see the first's inserts the
way a sequential server would), so a batch never contains two
requests for one owner — the later one stays queued, FIFO order
preserved, and rides the next pass.

Robustness contract:
- queue full → `SchedulerQueueFull` (the relay maps it to 503 +
  `Retry-After`): backpressure instead of unbounded handler threads.
- non-canonical timestamp widths never enter a batch: the engine's
  packed path rejects them batch-wide (`_pack_rows`), so they dispatch
  as singletons through the per-request `sync_wire`/`sync` path, which
  routes them to the host oracle BEFORE any side effect — the r5
  packed-receive contract, kept. Singletons still run ON the
  dispatcher thread: all store writes serialize there, so a fallback
  can never join an engine transaction left open on the shared
  connection.
- a poisoned batch (any engine-pass failure: every shard transaction
  rolled back, nothing committed) is retried ONCE as singletons, so
  one bad request can't fail its batchmates.
- `stop()` drains every queued request through full-size batches
  before the dispatcher exits; post-stop submits are rejected with
  `SchedulerQueueFull` (clients back off and retry elsewhere/later).

Shape stability: the engine pads every device batch to power-of-two
row buckets (`ops.bucket_size`), so varying micro-batch sizes inside a
bucket NEVER recompile the fused jit pipeline — pinned by
`tests/test_scheduler.py` via `engine.merkle_jit_cache_size()`.

Instrumented through `evolu_tpu.obs` (host-side only, no jax at import
time here — the engine, which does import jax, loads lazily on the
first batch): queue depth gauge, batch-size and batch-latency
histograms, coalesce/fallback/poison/reject counters
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from evolu_tpu.obs import anatomy, ledger, metrics, trace
from evolu_tpu.server.store import serve_single_request
from evolu_tpu.sync import aead, protocol
from evolu_tpu.utils.log import log


class SchedulerQueueFull(Exception):
    """Admission queue at capacity (or scheduler stopping): the caller
    should answer 503 with `retry_after` seconds."""

    def __init__(self, retry_after: float):
        super().__init__(f"sync scheduler queue full; retry after {retry_after}s")
        self.retry_after = retry_after


def _write_behind_full_type():
    """Lazy import for the except clause (evaluated at raise time):
    the scheduler stays importable without touching storage modules."""
    from evolu_tpu.storage.write_behind import WriteBehindFull

    return WriteBehindFull


class _Pending:
    """One enqueued request + its future. `single=True` marks a
    request the engine can't batch: it dispatches alone, still ON the
    dispatcher thread — every store write flows through one thread, so
    a fallback can never join an open engine transaction on the shared
    connection (NativeDatabase.transaction() JOINS when one is already
    open; a handler-thread write acked mid-batch would be rolled back
    with a poisoned batch)."""

    __slots__ = ("request", "single", "kept", "t_enqueue", "t_wall", "t_done",
                 "ctx", "done", "response", "error")

    def __init__(self, request: protocol.SyncRequest, single: bool = False):
        self.request = request
        self.single = single
        self.kept = 0  # batch closes that left it queued (_close_batch)
        self.t_enqueue = time.monotonic()
        self.t_wall = time.time()
        # The submitting handler thread's ambient trace context — the
        # dispatcher records this request's queue-wait span under it
        # and links it from the batch span (fan-in, obs/trace.py).
        self.ctx = trace.current()
        self.done = threading.Event()
        self.response: Optional[bytes] = None
        self.error: Optional[BaseException] = None

    def resolve(self, response: bytes) -> None:
        self.response = response
        self.t_done = time.monotonic()  # the wake's start (evolu_sched_wake_ms)
        self.done.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self.t_done = time.monotonic()
        self.done.set()


def _batchable(request: protocol.SyncRequest) -> bool:
    """Only canonical 46-char timestamps may enter a packed engine
    batch (`engine._pack_rows` rejects batch-wide otherwise); anything
    else takes the per-request path, whose host oracle is the error
    surface. Hex-CASE anomalies at canonical width stay batchable —
    the engine quarantines those owners to the host fold internally.
    Message CONTENT never factors in: the relay is E2EE-blind, so an
    aead-batch-v1 GCM record (sync/aead.py) batches exactly like an
    OpenPGP one — the engine stores and re-serves either verbatim."""
    return all(len(m.timestamp) == 46 for m in request.messages)


class SyncScheduler:
    """Admission + dispatch between relay handler threads and one
    `BatchReconciler`.

    `submit(request)` blocks the calling handler thread until its wire
    response (encoded SyncResponse bytes, byte-identical to the
    per-request `sync_wire` path — test-pinned) is ready, and raises
    `SchedulerQueueFull` when the bounded queue is at capacity.
    """

    def __init__(
        self,
        store,
        engine=None,
        mesh=None,
        max_batch: int = 32,
        max_wait_s: float = 0.005,
        max_queue: int = 256,
        retry_after_s: float = 1.0,
        submit_timeout_s: float = 120.0,
        write_behind=None,
        mesh_ctx=None,
        mesh_engine: bool = False,
    ):
        self.store = store
        # PR-12 sharded-engine wiring: an explicit
        # parallel.mesh.MeshContext (embedders/tests), or
        # mesh_engine=True to resolve the process-wide context lazily
        # on the dispatcher thread (get_mesh_context imports jax — it
        # must never run at relay import time). Several relays handing
        # traffic to one scheduler — or several schedulers sharing one
        # context — share ONE device pool: the mesh object keys every
        # compiled shard_map kernel, and placement is stable
        # process-wide.
        self._mesh_ctx = mesh_ctx
        self._mesh_engine = mesh_engine or (mesh_ctx is not None)
        # PR-11: a storage.write_behind.WriteBehindQueue makes the
        # engine serve from device-derived in-memory state and defer
        # SQLite to the queue's drain workers (one per storage shard
        # since PR-19). The scheduler's jobs:
        # construct the engine with it, convert its backpressure into
        # the 503 + Retry-After answer (queue-full stalls admission,
        # never drops), and run every DIRECT store write (singleton
        # fallbacks — non-batchable shapes, poison retries) behind the
        # queue's drain barrier so sync_wire reads and writes only
        # committed state.
        self._write_behind = write_behind
        self.max_batch = max(1, int(max_batch))
        self.max_wait_s = float(max_wait_s)
        self.max_queue = int(max_queue)
        self.retry_after_s = float(retry_after_s)
        self.submit_timeout_s = float(submit_timeout_s)
        self._mesh = mesh
        self._engine = engine
        self._own_engine = engine is None
        self._engine_broken: Optional[BaseException] = None
        self._cv = threading.Condition()
        self._queue: List[_Pending] = []
        # What the last _close_batch kept back, {reason: requests}:
        # written under the lock and posted by the same dispatcher
        # thread with that batch's queue waits (_record_queue_waits).
        self._kept: dict = {}
        self._stopping = False
        self._stopped = threading.Event()
        self._thread = threading.Thread(
            target=self._dispatch_loop, daemon=True, name="evolu-sched"
        )
        self._thread.start()

    # -- admission (handler threads) --

    def depth(self) -> int:
        """Current admission-queue occupancy (0..max_queue) — the
        load signal the fleet `/health` detail exposes so operators
        (and future load-aware placement) can see saturation per
        relay without scraping the full registry."""
        with self._cv:
            return len(self._queue)

    def submit(self, request: protocol.SyncRequest) -> bytes:
        """Serve one request: coalesced through the next engine pass,
        or as a singleton dispatch for shapes the engine can't batch —
        either way serialized on the dispatcher thread (see _Pending)."""
        p = _Pending(request, single=not _batchable(request))
        with self._cv:
            if self._stopping or len(self._queue) >= self.max_queue:
                metrics.inc("evolu_sched_rejected_total")
                raise SchedulerQueueFull(self.retry_after_s)
            self._queue.append(p)
            metrics.set_gauge("evolu_sched_queue_depth", len(self._queue))
            self._cv.notify()
        if not p.done.wait(self.submit_timeout_s):
            raise TimeoutError(
                f"sync scheduler did not serve the request within "
                f"{self.submit_timeout_s}s"
            )
        # The hand-over of the interpreter from the dispatcher (which
        # stamped t_done just before setting the event) to this handler.
        metrics.observe("evolu_sched_wake_ms",
                        (time.monotonic() - p.t_done) * 1e3)
        if p.error is not None:
            raise p.error
        return p.response  # type: ignore[return-value]

    # -- dispatch (one background thread) --

    def _dispatch_loop(self) -> None:
        # evolu_sched_dispatcher_seconds_total{state}: `busy` is
        # _close_batch + _run_batch, `cpu` this thread's own CPU seconds
        # (time.thread_time) across the same extent, `idle` everything
        # between two busy extents (the empty-queue wait and the
        # max_wait_s coalescing wait), so idle + busy is the thread's
        # wall time. busy/(busy+idle) near 1: the relay is one thread
        # long; cpu/busy well under 1: the dispatcher waits — for the
        # interpreter lock, SQLite's shard threads or the device.
        t_idle = time.perf_counter()
        t_free = 0.0  # time.monotonic() at the previous pass's end
        try:
            while True:
                with self._cv:
                    while not self._queue and not self._stopping:
                        self._cv.wait()
                    if not self._queue:
                        return  # stopping + drained
                    # A batch stays open max_wait_s from the moment the
                    # dispatcher could first have taken it: the LATER of
                    # its oldest request's enqueue and the previous
                    # pass's end. While a pass took 20-30 ms it was the
                    # coalescing window itself; at 6-14 ms (ISSUE 41) a
                    # dispatcher that closes at once what piled up
                    # behind it runs passes of three requests back to
                    # back and keeps the interpreter lock from the
                    # handlers and the acceptor: 7 % fewer rounds a
                    # second than before the pass got cheaper (PERF.md
                    # §6, PR 41). stop() waives the wait so the drain
                    # runs at full batch size without deadline stalls.
                    deadline = max(self._queue[0].t_enqueue, t_free) + self.max_wait_s
                    while len(self._queue) < self.max_batch and not self._stopping:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                    t_busy, cpu_busy = time.perf_counter(), time.thread_time()
                    batch = self._close_batch()
                    metrics.set_gauge("evolu_sched_queue_depth", len(self._queue))
                try:
                    self._run_batch(batch)
                except BaseException:
                    for p in batch:  # already popped — fail, don't hang
                        if not p.done.is_set():
                            p.fail(RuntimeError("sync scheduler dispatcher exited"))
                    raise
                now = time.perf_counter()
                metrics.inc("evolu_sched_dispatcher_seconds_total",
                            t_busy - t_idle, state="idle")
                metrics.inc("evolu_sched_dispatcher_seconds_total",
                            now - t_busy, state="busy")
                metrics.inc("evolu_sched_dispatcher_seconds_total",
                            time.thread_time() - cpu_busy, state="cpu")
                t_idle, t_free = now, time.monotonic()
        finally:
            # If the loop died abnormally (BaseException out of
            # _run_batch — e.g. KeyboardInterrupt mid-pass), blocked
            # submitters must not hang until their timeout.
            with self._cv:
                dead, self._queue = self._queue, []
                self._stopping = True
            for p in dead:
                p.fail(RuntimeError("sync scheduler dispatcher exited"))
            self._stopped.set()

    def _close_batch(self) -> List[_Pending]:
        """Pop the next dispatch, FIFO, called under the lock. A
        `single` at the queue head dispatches alone; otherwise up to
        max_batch DISTINCT-owner batchable requests. A second request
        for an owner already in the batch stays queued (its response
        must observe the first request's inserts exactly as a
        sequential server's would), and once anything of an owner is
        kept back (same-owner duplicate, single, or capacity), every
        later request of that owner is kept too — per-owner FIFO is
        never reordered.

        Every request a close keeps counts once in `self._kept` under
        the reason that kept it (`evolu_sched_deferred_total{reason}`:
        `owner_blocked` behind a kept request of its owner,
        `same_owner` an owner already in the batch, `single` a
        non-batchable request or the queue behind the one dispatched
        alone, `capacity`) and once in its own `kept`
        (`evolu_sched_passes_waited`, observed at the close that takes
        it). Counting decides nothing: what is kept is what was."""
        kept = self._kept = {}
        if self._queue[0].single:
            head = self._queue.pop(0)
            for p in self._queue:
                p.kept += 1
            if self._queue:
                kept["single"] = len(self._queue)
            return [head]
        batch: List[_Pending] = []
        owners: set = set()
        keep: List[_Pending] = []
        blocked: set = set()
        for p in self._queue:
            uid = p.request.user_id
            if uid in blocked:
                reason = "owner_blocked"
            elif p.single:
                reason = "single"
            elif uid in owners:
                reason = "same_owner"
            elif len(batch) >= self.max_batch:
                reason = "capacity"
            else:
                owners.add(uid)
                batch.append(p)
                continue
            blocked.add(uid)
            keep.append(p)
            p.kept += 1
            kept[reason] = kept.get(reason, 0) + 1
        # Anything kept is seen by the next loop iteration's queue
        # check — no new arrival needed to wake the dispatcher.
        self._queue = keep
        return batch

    def _record_queue_waits(self, batch: List[_Pending]) -> None:
        """Per-request queue wait (enqueue → the batch close that takes
        it), measured against ONE dispatch instant: the
        `evolu_sched_queue_wait_ms` histogram for every request, and a
        `sched.queue` span under the request's own trace where it has
        one — one leg of the queue-wait / engine-time / respond split.
        Under the same acquisition of the registry's lock: how many
        closes had kept each request (`evolu_sched_passes_waited`) and
        what this close kept (`evolu_sched_deferred_total{reason}`)."""
        t_dispatch = time.monotonic()
        waits = [(t_dispatch - p.t_enqueue) * 1e3 for p in batch]
        kept, self._kept = self._kept, {}  # posted once, by its own close
        metrics.observe_many(
            [("evolu_sched_queue_wait_ms", w, {}) for w in waits]
            + [("evolu_sched_passes_waited", p.kept, {}) for p in batch],
            also_inc=[("evolu_sched_deferred_total", n, {"reason": reason})
                      for reason, n in kept.items()])
        for p, wait_ms in zip(batch, waits):
            if p.ctx is not None:
                trace.record_span("sched.queue", p.ctx, p.t_wall, wait_ms)

    def _run_batch(self, batch: List[_Pending]) -> None:
        if not batch:
            return
        if batch[0].single:
            p = batch[0]
            metrics.inc("evolu_sched_fallback_total", reason="non_canonical")
            # Ledger TALLY (outside the flow equations — the request's
            # flow still terminates through the store path below): the
            # server-side canonicality bounce.
            ledger.count(ledger.BOUNCE_NON_CANONICAL, len(p.request.messages),
                         owner=p.request.user_id)
            self._record_queue_waits(batch)
            sspan = trace.start_span("sched.single", parent=p.ctx,
                                     attrs={"owner": p.request.user_id})
            try:
                with sspan, trace.use(sspan.context):
                    p.resolve(self._serve_single(p.request))
            except Exception as e:  # noqa: BLE001 - per-request error
                p.fail(e)
            return
        t0 = time.perf_counter()
        metrics.inc("evolu_sched_batches_total")
        metrics.observe(
            "evolu_sched_batch_requests", len(batch), buckets=metrics.COUNT_BUCKETS
        )
        self._record_queue_waits(batch)
        # The fan-in span: ONE engine pass serves N requests from N
        # different traces, so the batch span LINKS the request spans
        # (it cannot parent them — a span has one trace). It roots its
        # own trace, is force-sampled whenever any linked request is
        # sampled, and GET /trace/<request-id> surfaces it through the
        # link index. Kernel spans opened inside the engine pass
        # (utils/log.py span()) nest under it via the ambient context.
        # (start_span already records whenever any sampled link is
        # present — no force_sample needed here.)
        links = [p.ctx for p in batch if p.ctx is not None]
        bspan = trace.start_span(
            "engine.batch", links=links,
            attrs={
                "requests": len(batch),
                "owners": len({p.request.user_id for p in batch}),
            },
        )
        # pass_respond (obs.anatomy): the engine starts it where its
        # apply leg ends, this thread stops it at the batch_ms
        # observation — ledger terminals, the wire respond, resolving
        # the futures, the recompile sentinel. With the engine's seven
        # pass_* stages it tiles the pass.
        respond = anatomy.stage("pass_respond")
        try:
            self._run_pass(batch, bspan, respond, t0)
        finally:
            respond.stop()  # a no-op unless something escaped _run_pass

    def _run_pass(self, batch: List[_Pending], bspan, respond, t0: float) -> None:
        """One engine pass and its answers: `_run_batch` from the batch
        span's start to the batch_ms observation."""
        try:
            engine = self._ensure_engine()
            with trace.use(bspan.context):
                outs = engine.run_batch_wire([p.request for p in batch], respond)
            bspan.end()
        except _write_behind_full_type() as e:
            # Write-behind admission backpressure: nothing was served
            # or persisted (the engine raises BEFORE the log ACK).
            # This is flow control, not poison — answer every batch
            # member 503 + Retry-After instead of slamming the
            # singleton path with the very writes the queue stalled.
            bspan.set_attr("backpressure", True)
            bspan.end()
            # Counting: the queue already counted the stall
            # (evolu_wb_stalls_total) and the relay counts the 503
            # answer (evolu_relay_backpressure_total) — no fallback
            # counter here: these requests were NOT served on the
            # per-request path, they were shed as flow control.
            for p in batch:
                p.fail(SchedulerQueueFull(e.retry_after))
            return
        except Exception as e:  # noqa: BLE001 - poison isolation
            # (BaseException — KeyboardInterrupt/SystemExit — is NOT
            # poison: it propagates, and the loop's finally fails any
            # still-queued futures.) Every shard transaction rolled
            # back (engine contract): nothing committed, so the
            # singleton retry is exact — and it isolates the poison to
            # the one request that carries it; batchmates succeed.
            bspan.set_attr("poisoned", True)
            bspan.set_attr("error", repr(e))
            bspan.end()
            metrics.inc("evolu_sched_poisoned_batches_total")
            log("server", "scheduler batch poisoned; retrying as singletons",
                error=repr(e), requests=len(batch))
            for p in batch:
                try:
                    response = self._serve_single(p.request)
                except Exception as pe:  # noqa: BLE001
                    # No ledger terminal here: the relay's 500 answer
                    # counts reject.invalid — the poisoned engine pass
                    # posted nothing (rolled back), and the singleton
                    # store path posts only on commit, so the retry can
                    # never double-count.
                    p.fail(pe)
                else:
                    metrics.inc("evolu_sched_fallback_total", reason="poison_retry")
                    p.resolve(response)
            self._observe_jit_caches(batch)
            respond.stop()
            metrics.observe("evolu_sched_batch_ms", (time.perf_counter() - t0) * 1e3,
                            exemplar=bspan.trace_id)
            return
        metrics.inc("evolu_sched_coalesced_requests_total", len(batch))
        n_v2 = sum(aead.count_v2(p.request.messages) for p in batch)
        if n_v2:
            # The fused engine pass just carried v2 ciphertext end to
            # end (store + Merkle + response re-serve, all opaque) —
            # the counter operators correlate with the relay-ingest
            # mix to confirm negotiated traffic rides the BATCHED path,
            # not the singleton fallback (docs/OBSERVABILITY.md).
            metrics.inc("evolu_crypto_v2_batched_messages_total", n_v2)
        for p, out in zip(batch, outs):
            p.resolve(out)
        self._observe_jit_caches(batch)
        respond.stop()
        metrics.observe("evolu_sched_batch_ms", (time.perf_counter() - t0) * 1e3,
                        exemplar=bspan.trace_id)

    def _observe_jit_caches(self, batch) -> None:
        """Recompile sentinel, after each engine pass: diff the merkle/
        mesh jit cache sizes into gauges + a recompiles counter, flight
        event on growth (engine.observe_jit_caches). Skipped until an
        engine exists — importing the engine module here would pull jax
        onto relays that never ran a batch. Never raises."""
        if self._engine is None:
            return
        try:
            from evolu_tpu.server import engine as eng_mod

            eng_mod.observe_jit_caches(
                sum(len(p.request.messages) for p in batch)
            )
        except Exception:  # noqa: BLE001,S110 - sentinel must not fail a batch
            pass

    def _ensure_engine(self):
        """The BatchReconciler, created lazily on the dispatcher thread
        (its import pulls jax — nothing here touches a backend until
        the first batch). A broken engine (e.g. no usable jax backend)
        is remembered so every batch degrades to singletons without
        re-paying the failed construction."""
        if self._engine_broken is not None:
            raise self._engine_broken
        if self._engine is None:
            try:
                from evolu_tpu.server.engine import BatchReconciler

                if self._mesh_engine and self._mesh_ctx is None:
                    from evolu_tpu.parallel.mesh import get_mesh_context
                    from evolu_tpu.utils.config import default_config

                    self._mesh_ctx = get_mesh_context(
                        default_config.mesh_devices
                    )
                self._engine = BatchReconciler(
                    self.store, self._mesh, write_behind=self._write_behind,
                    mesh_ctx=self._mesh_ctx,
                )
            except Exception as e:  # noqa: BLE001
                self._engine_broken = e
                raise
        return self._engine

    def _serve_single(self, request: protocol.SyncRequest) -> bytes:
        """The per-request path — exactly what the relay ran before the
        scheduler existed (ONE recipe, shared with the non-batching
        do_POST branch): fused C wire serve, object-path fallback
        (which is where non-canonical shapes reach the host oracle
        before any side effect). Only ever called on the dispatcher
        thread, so it can never interleave with an open engine
        transaction on the shared store connection."""
        if self._write_behind is not None:
            # Direct store writes (the host-oracle / non-batchable
            # path) must observe and produce committed state: drain
            # everything, hold the drain lock for the duration, and
            # let the queue's serving caches fall back to SQLite truth.
            with self._write_behind.drain_barrier():
                return serve_single_request(self.store, request)
        return serve_single_request(self.store, request)

    def stop(self) -> None:
        """Drain then shut down (idempotent — the relay and an
        embedding caller may both stop a shared scheduler): everything
        already queued is served (full-size batches, no deadline
        waits); new submits are rejected with `SchedulerQueueFull`."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        self._stopped.wait(timeout=max(30.0, self.submit_timeout_s))
        self._thread.join(timeout=5.0)
        if self._own_engine:
            with self._cv:
                engine, self._engine = self._engine, None
            if engine is not None:
                engine.close()


def format_retry_after(seconds: float) -> str:
    """RFC 7231 Retry-After is integer delay-seconds; emit the integer
    form when integral and the bare float otherwise (our client parses
    either — sub-second values matter for tests and local deploys)."""
    f = float(seconds)
    return str(int(f)) if f.is_integer() else repr(f)
