"""Stage-anatomy plane (ISSUE 16): the fused reconcile pipeline as a
declarative stage registry with roofline-priced floors.

CLAUDE.md's hardest-won rule is "re-ablate stages after every
restructure" (the r4→r5 share shift: hash read 0.885 → 1.29 ms after
the sort shrank) — yet until this module the v5e/CPU cost model lived
as prose in docs/BENCHMARKS.md and ablation was a hand-run ritual.
Here the model becomes data:

- `STAGES` — the ordered registry over the fused reconcile pipeline
  (packed-key sort → plan/compare → hash render → Merkle minute fold →
  compact-delta encode → pull wave) plus the runtime seams the engine
  times per batch (device dispatch / pull wave / host apply). Each
  stage declares its inputs, outputs, and a priced floor as cost-law
  terms; `benchmarks/stage_anatomy.py` builds its stage-truncated
  timed variants from exactly these names and asserts the output
  arity against `outputs` (registry drift fails loudly, not quietly).
- `COST_LAWS` — the machine-readable encoding of the recorded cost
  laws, keyed by jax `device_kind` (docs/BENCHMARKS.md r3-r5 for v5e;
  the CPU row is transcribed from this container's seeding run of
  stage_anatomy.py). `floor_ms` prices a stage from them. Floors are
  the RECORDED BEST for the device, not an ideal roofline: "over
  floor" means "regressed ≥ FLOOR_FACTOR× from what this repo has
  measured", which is actionable, where "above DRAM bandwidth ideal"
  never is. A law a device has no measurement for is ABSENT, and
  every stage priced by it is unpriced ("not measured") there.
- `stage` / `record_stage` / `record_span` — the runtime accountant
  feeding the `evolu_stage_*` metrics family (`stage` is the ONE
  primitive the served path's seams use: it times an interval where
  the work happens, records it here, and mirrors it into the
  profiler's trace and the distributed trace): per-stage histograms +
  totals, an
  online (decayed) least-squares fit per stage separating the fixed
  per-dispatch intercept from the per-row slope, per-batch
  device-dispatch / pull-wave / host-apply share gauges (EWMA over
  recent batches), and `evolu_stage_over_floor_total` flags when a
  stage runs above FLOOR_FACTOR× its priced floor.

This module is part of `evolu_tpu.obs` and therefore MUST NOT import
jax (tests/test_import_hygiene.py): the device kind is pushed in via
`set_device_kind` from the jax side (parallel/mesh.py), and every value
recorded here is a host-side Python float the hot paths already hold.
The accountant follows `metrics.registry.enabled` — disabled, a
record call is one attribute read.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from evolu_tpu.obs import metrics, trace
from evolu_tpu.utils import log as _log  # jax-free at import (annotations load lazily)

# --------------------------------------------------------------------
# Cost laws, keyed by jax `device_kind`: ms per 1M rows (per_1m_rows),
# MB/s (bandwidth), rows/s, or plain ms (fixed). The v5e per-row laws
# are the slope measurements behind docs/BENCHMARKS.md r3-r5, taken
# before PR 1; none has been re-measured on the attached chip, and the
# fixed per-dispatch cost and the device→host pull bandwidth there are
# NOT MEASURED — so those two laws are absent and `pull_wave` /
# `device_dispatch` are unpriced on that device until a chip run gives
# a number. The cpu numbers are this container's 8-device-virtual-mesh
# seeding run of benchmarks/stage_anatomy.py
# (docs/baselines/anatomy.cpu.json) — the laws and the baseline
# artifact are the same measurement, so the runtime flags only genuine
# regressions from it.
# --------------------------------------------------------------------

V5E = "TPU v5 lite"  # jax.devices()[0].device_kind on a v5e chip

COST_LAWS: Dict[str, Dict[str, float]] = {
    V5E: {
        # lax.sort, 1M rows: packed-i64 single key ~1.5 ms + ~0.75 ms
        # per u64 payload carried through it (r3, re-measured r5).
        "sort_key_ms_per_1m": 1.5,
        "sort_payload_ms_per_1m": 0.75,
        # The two segmented max scans + flag algebra of the planner
        # tail (r5 in-pipeline ablation: "scans 0.54").
        "plan_scan_pair_ms_per_1m": 0.54,
        # u32 hi/lo divmod render + murmur fold (r5: "hash 0.24" after
        # the batch-lax.cond exact-division rework).
        "hash_render_ms_per_1m": 0.24,
        # Tile-local (owner, minute) grouping + segmented XOR (r5:
        # "minute 0.36").
        "minute_fold_ms_per_1m": 0.36,
        # Compact-delta encode tail: one more stable packed sort with
        # two payloads (engine._compact_segments_tail) = key + 2
        # payloads by the sort law.
        "delta_encode_ms_per_1m": 3.0,
        # Host apply: packed C ingest measured ~0.72M rows/s/core
        # (docs/BENCHMARKS.md r7/r12 btree-bound ingest) — a host law.
        "host_apply_rows_per_s": 720_000.0,
    },
    "cpu": {
        # Seeded from benchmarks/stage_anatomy.py on this container
        # (8-device virtual CPU mesh, N=2^19, INTERLEAVED per-rep
        # marginals scaled to 1M rows; docs/baselines/anatomy.cpu.json
        # is the adjacent reproducibility run — big-stage marginals
        # agree within ~3%). The key_sort marginal
        # (446 ms/1M for key + 2 payloads) is split key/payload by
        # the v5e 2:1 ratio; the generic-scan-heavy plan/minute
        # stages dominate on CPU exactly as docs/BENCHMARKS.md r7
        # recorded (sort share collapses, scans blow up ~4000× vs
        # the v5e law).
        "sort_key_ms_per_1m": 223.0,
        "sort_payload_ms_per_1m": 111.5,
        "plan_scan_pair_ms_per_1m": 2220.0,
        "hash_render_ms_per_1m": 250.0,
        "minute_fold_ms_per_1m": 411.0,
        "delta_encode_ms_per_1m": 658.0,
        # Dispatch intercept of the timed loop at N=2^19 (jit-call +
        # arg handling) and the best measured host-copy bandwidth of a
        # kernel-output wave (host-local memcpy — run-to-run spread
        # 2.1-7.6 GB/s, the floor uses the best).
        "fixed_dispatch_ms": 261.0,
        "pull_mb_per_s": 7650.0,
        "host_apply_rows_per_s": 720_000.0,
    },
}


@dataclass(frozen=True)
class Stage:
    """One pipeline stage: identity for the ablation harness (inputs /
    outputs name the dataflow; the harness asserts variant arity from
    `outputs`) plus the priced floor as (law_key, unit) terms, where
    unit ∈ {per_1m_rows, bandwidth, fixed, device_pipeline}."""

    name: str
    kind: str  # "device" (ablatable kernel stage) | "host" | "runtime"
    description: str
    inputs: Tuple[str, ...]
    outputs: Tuple[str, ...]
    price: Tuple[Tuple[str, str], ...] = ()


STAGES: Tuple[Stage, ...] = (
    Stage(
        "key_sort", "device",
        "winner flags + packed owner|cell|idx|flags i64 key + lax.sort "
        "with the two u64 HLC payloads (reconcile._shard_kernel head)",
        inputs=("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix"),
        outputs=("key_sorted", "k1_sorted", "k2_sorted"),
        price=(("sort_key_ms_per_1m", "per_1m_rows"),
               ("sort_payload_ms_per_1m", "per_1m_rows"),
               ("sort_payload_ms_per_1m", "per_1m_rows")),
    ),
    Stage(
        "plan_compare", "device",
        "sorted-key field unpack + segmented max scans + LWW flag "
        "algebra (ops.merge.masks_from_sorted_flags)",
        inputs=("key_sorted", "k1_sorted", "k2_sorted"),
        outputs=("xor_sorted", "upsert_sorted", "idx_sorted"),
        price=(("plan_scan_pair_ms_per_1m", "per_1m_rows"),),
    ),
    Stage(
        "hash_render", "device",
        "HLC key unpack + canonical timestamp render + murmur3 hash, "
        "masked by the xor plan, + XOR-allreduced batch digest",
        inputs=("k1_sorted", "k2_sorted", "xor_sorted"),
        outputs=("hashes", "digest"),
        price=(("hash_render_ms_per_1m", "per_1m_rows"),),
    ),
    Stage(
        "minute_fold", "device",
        "tile-local (owner, minute) grouping + segmented XOR of the "
        "row hashes (ops.merkle_ops.owner_minute_segments)",
        inputs=("owner_ix", "k1_sorted", "hashes", "xor_sorted"),
        outputs=("owner_sorted", "minute_sorted", "seg_end", "seg_xor",
                 "valid_sorted"),
        price=(("minute_fold_ms_per_1m", "per_1m_rows"),),
    ),
    Stage(
        "delta_encode", "device",
        "compact-delta wire encode: pack owner<<32|minute, stable "
        "float-segments-to-front sort, segment count (the "
        "engine._compact_segments_tail shape, 16B/row upload form)",
        inputs=("owner_sorted", "minute_sorted", "seg_end", "seg_xor",
                "valid_sorted"),
        outputs=("delta_packed", "delta_xor", "seg_count"),
        price=(("delta_encode_ms_per_1m", "per_1m_rows"),),
    ),
    Stage(
        "pull_wave", "host",
        "one to_host_many transfer wave of the kernel outputs, priced "
        "by bytes over the device's pull bandwidth law",
        inputs=("device_outputs",),
        outputs=("host_arrays",),
        price=(("pull_mb_per_s", "bandwidth"),),
    ),
    Stage(
        "device_dispatch", "runtime",
        "engine.start_batch: pack + native parse + device dispatch + "
        "async transfer start (no database access) — the fixed "
        "per-dispatch cost plus the whole device pipeline at batch size",
        inputs=("sync_requests",),
        outputs=("staged_batch",),
        price=(("fixed_dispatch_ms", "fixed"),
               ("device_pipeline", "device_pipeline")),
    ),
    Stage(
        "host_apply", "runtime",
        "the btree+tree materialization leg: per-shard C inserts + "
        "delta decode + Merkle tree folds + one atomic commit per "
        "shard — engine.finish_batch synchronously, or the per-shard "
        "write-behind drain workers in deferred mode (each worker "
        "records its shard's batches with a shard= label)",
        inputs=("staged_batch",),
        outputs=("responses",),
        price=(("host_apply_rows_per_s", "rows_per_s"),),
    ),
)

_STAGE_BY_NAME: Dict[str, Stage] = {s.name: s for s in STAGES}

# Runtime stages whose EWMA durations form the per-batch share gauges.
RUNTIME_SHARE_STAGES = ("device_dispatch", "pull_wave", "host_apply")

# kernel:* span targets folded into the family get a priced floor when
# their work maps onto registry stages; everything else records
# unpriced (no floor → never flagged).
_SPAN_FLOOR_STAGES: Dict[str, Tuple[str, ...]] = {
    # reconcile_owner_batches wraps dispatch + device pipeline + pull.
    "kernel:reconcile": ("device_dispatch",),
    # The server Merkle kernels run hash + minute fold + delta encode.
    "kernel:merkle": ("hash_render", "minute_fold", "delta_encode"),
}

FLOOR_FACTOR = float(os.environ.get("EVOLU_STAGE_FLOOR_FACTOR", "4.0"))
_WARMUP_RECORDS = 2  # first records include compile; never flag them
_DECAY = 0.98  # sliding exponential window for the per-stage fit
_EWMA_ALPHA = 0.2


def registry_digest() -> str:
    """crc32 fingerprint of the registry + cost laws. A hard gate in
    docs/baselines/anatomy.<platform>.json (compare_baselines treats
    *digest* keys as exact-match): restructuring the registry or
    re-pricing a law without re-ablating fails CI until the baseline
    is re-recorded from a real run."""
    doc = {
        "stages": [
            (s.name, s.kind, s.inputs, s.outputs, s.price) for s in STAGES
        ],
        "laws": COST_LAWS,
    }
    return f"{zlib.crc32(json.dumps(doc, sort_keys=True).encode()) & 0xFFFFFFFF:08x}"


def floor_ms(stage: str, rows: int = 0, nbytes: int = 0,
             device_kind: Optional[str] = None) -> Optional[float]:
    """Priced floor for `stage` at this batch shape, in ms — or None,
    "not measured": the stage is unregistered, or the device has no
    law for one of its terms. A device asked for BY NAME that has no
    laws at all is an error (KeyError), not a silent zero; the runtime
    accountant prices for whatever device the process came up on,
    passes no name, and reads an unknown device as unpriced."""
    if device_kind is None:
        laws = COST_LAWS.get(_acct.device_kind)
        if laws is None:
            return None
    else:
        laws = COST_LAWS[device_kind]
    return _price(stage, laws, rows, nbytes)


def _price(stage: str, laws: Dict[str, float], rows: int,
           nbytes: int) -> Optional[float]:
    if stage in _SPAN_FLOOR_STAGES:
        return _sum_priced(
            _price(s, laws, rows, nbytes) for s in _SPAN_FLOOR_STAGES[stage]
        )
    st = _STAGE_BY_NAME.get(stage)
    if st is None:
        return None
    terms = []
    for law_key, unit in st.price:
        if unit == "device_pipeline":
            terms.append(_sum_priced(
                _price(s.name, laws, rows, 0)
                for s in STAGES if s.kind == "device"
            ))
            continue
        law = laws.get(law_key)
        if law is None:
            terms.append(None)
        elif unit == "per_1m_rows":
            terms.append(law * (rows / 1e6))
        elif unit == "fixed":
            terms.append(law)
        elif unit == "bandwidth":
            terms.append(nbytes / (law * 1e6) * 1e3)
        elif unit == "rows_per_s":
            terms.append(rows / law * 1e3)
    return _sum_priced(terms)


def _sum_priced(terms) -> Optional[float]:
    """Sum of price terms; one unmeasured term unprices the whole."""
    total = 0.0
    for t in terms:
        if t is None:
            return None
        total += t
    return total


class _StageAccountant:
    """Per-stage running state behind the evolu_stage_* family. All
    host-side dict/float arithmetic under one lock (engine pull thread
    + relay handler threads record concurrently)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.device_kind = "unknown"
        self._stats: Dict[str, dict] = {}

    def _stage_state(self, stage: str) -> dict:
        st = self._stats.get(stage)
        if st is None:
            st = self._stats[stage] = {
                "count": 0, "total_ms": 0.0, "ewma_ms": None,
                # Decayed least-squares accumulators over (rows, ms).
                "n": 0.0, "sx": 0.0, "sy": 0.0, "sxx": 0.0, "sxy": 0.0,
                "slope_ns_per_row": None, "fixed_ms": None,
                "floor_ms": None, "over_floor": 0,
            }
        return st

    def record(self, stage: str, seconds: float, rows: int = 0,
               nbytes: int = 0, shard: Optional[int] = None,
               wait: Optional[float] = None) -> None:
        if not metrics.registry.enabled:
            return
        ms = seconds * 1e3
        labels = {"stage": stage}
        observed = [("evolu_stage_ms", ms, labels)]
        if wait is not None:
            # What `stage` measured of it off the CPU (its docstring).
            observed.append(("evolu_stage_wait_ms", wait * 1e3, labels))
        if shard is not None:
            # Per-shard split of a stage that runs concurrently per
            # shard (the write-behind drain): shard labels are bounded
            # by store topology, far inside the 512-per-family cap.
            observed.append(("evolu_stage_shard_ms", ms,
                             {"stage": stage, "shard": str(shard)}))
        # One acquisition of the registry's lock for the histograms and
        # the totals: eleven stages a served pass record here, on the
        # thread that is the relay's bottleneck.
        metrics.observe_many(observed, also_inc=(
            ("evolu_stage_seconds_total", seconds, labels),
            ("evolu_stage_rows_total", rows, labels),
            ("evolu_stage_bytes_total", nbytes, labels)))
        floor = floor_ms(stage, rows=rows, nbytes=nbytes)
        with self._lock:
            st = self._stage_state(stage)
            st["count"] += 1
            st["total_ms"] += ms
            st["ewma_ms"] = (
                ms if st["ewma_ms"] is None
                else (1 - _EWMA_ALPHA) * st["ewma_ms"] + _EWMA_ALPHA * ms
            )
            st["floor_ms"] = floor
            flagged = (
                floor is not None and floor > 0.0
                and st["count"] > _WARMUP_RECORDS
                and ms > FLOOR_FACTOR * floor
            )
            if flagged:
                st["over_floor"] += 1
            slope_fixed = None
            if rows > 0:
                # Decayed accumulators: the fit tracks the recent
                # regime, so a restructure shows up within ~50 batches
                # instead of being averaged against history forever.
                for k in ("n", "sx", "sy", "sxx", "sxy"):
                    st[k] *= _DECAY
                st["n"] += 1.0
                st["sx"] += rows
                st["sy"] += ms
                st["sxx"] += float(rows) * rows
                st["sxy"] += rows * ms
                n, sx, sy, sxx, sxy = (
                    st["n"], st["sx"], st["sy"], st["sxx"], st["sxy"]
                )
                var = n * sxx - sx * sx
                if n >= 2.0 and var > 1e-9:
                    slope_ms_per_row = (n * sxy - sx * sy) / var
                    fixed = (sy - slope_ms_per_row * sx) / n
                    st["slope_ns_per_row"] = max(slope_ms_per_row, 0.0) * 1e6
                    st["fixed_ms"] = max(fixed, 0.0)
                    slope_fixed = (st["slope_ns_per_row"], st["fixed_ms"])
            shares = None
            if stage in RUNTIME_SHARE_STAGES:
                ewmas = {
                    s: self._stats[s]["ewma_ms"]
                    for s in RUNTIME_SHARE_STAGES
                    if s in self._stats and self._stats[s]["ewma_ms"] is not None
                }
                total = sum(ewmas.values())
                if total > 0:
                    shares = {s: v / total for s, v in ewmas.items()}
        # Gauges outside the lock: metrics has its own.
        if floor is not None and floor > 0.0:
            metrics.set_gauge("evolu_stage_floor_ms", floor, stage=stage)
            metrics.set_gauge("evolu_stage_over_floor_ratio", ms / floor,
                              stage=stage)
            if flagged:
                metrics.inc("evolu_stage_over_floor_total", stage=stage)
        if slope_fixed is not None:
            # The fixed per-dispatch intercept separated from the
            # per-row slope — the wall/count trap, live.
            metrics.set_gauge("evolu_stage_slope_ns_per_row",
                              slope_fixed[0], stage=stage)
            metrics.set_gauge("evolu_stage_fixed_ms", slope_fixed[1],
                              stage=stage)
        if shares is not None:
            for s, v in shares.items():
                metrics.set_gauge("evolu_stage_share", v, stage=s)

    def payload(self) -> dict:
        with self._lock:
            stages = {
                name: {
                    k: st[k]
                    for k in ("count", "total_ms", "ewma_ms",
                              "slope_ns_per_row", "fixed_ms", "floor_ms",
                              "over_floor")
                }
                for name, st in sorted(self._stats.items())
            }
        ewmas = {
            s: stages[s]["ewma_ms"]
            for s in RUNTIME_SHARE_STAGES
            if s in stages and stages[s]["ewma_ms"] is not None
        }
        total = sum(ewmas.values())
        for s, v in ewmas.items():
            stages[s]["share"] = v / total if total > 0 else None
        return {
            "device_kind": self.device_kind,
            "floor_factor": FLOOR_FACTOR,
            "registry_digest": registry_digest(),
            "stages": stages,
        }

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


_acct = _StageAccountant()


def set_device_kind(device_kind: str) -> None:
    """Push the device kind in from the jax side (parallel/mesh.py at
    mesh creation) — this module must never ask jax itself. A device
    without laws records every stage unpriced (never flagged)."""
    _acct.device_kind = str(device_kind)


def get_device_kind() -> str:
    return _acct.device_kind


def record_stage(stage: str, seconds: float, rows: int = 0,
                 nbytes: int = 0, shard: Optional[int] = None) -> None:
    """Record one execution of a stage whose interval is only known
    afterwards (the write-behind drain workers, with their shard
    index); a seam that can bracket its work uses `stage`."""
    _acct.record(stage, seconds, rows=rows, nbytes=nbytes, shard=shard)


def _edge(cpu: bool):
    """This instant on the calling thread, as (wall, cpu):
    `time.perf_counter` and, where the kind of stage asks for it,
    `time.thread_time`. None with the registry disabled: no clock is
    read and the interval goes nowhere."""
    if metrics.registry.enabled:
        return time.perf_counter(), (time.thread_time() if cpu else None)
    return None


class stage:
    """The ONE stage primitive: time an interval on the thread that
    does the work and record it where `record_stage` does.

        with anatomy.stage("pull_wave") as wave:
            ...
            wave.nbytes = n          # known only inside the interval

    `time.perf_counter` at both edges feeds `record_stage` (so the
    `evolu_stage_*` family always sees it); with `utils.log`'s trace
    annotations enabled the same interval is also a
    `jax.profiler.TraceAnnotation` named ``evolu/<name>`` — in the
    `.xplane.pb` on the profiler's own clock, beside the device's
    `XLA Ops`, no calibration between clocks; and under an ambient
    sampled `obs.trace` context (read at `start`) it lands in the
    distributed trace too, as `log.span` does, so `GET /trace/<id>`
    shows the same names. Annotations off: one `is None` test beyond
    `record_stage`; registry disabled: the annotation and no clock.

    `time.thread_time` is read at the same edges, and the interval's
    `wait` = wall − this thread's own CPU time (user and system) is
    recorded beside it as `evolu_stage_wait_ms{stage}`: the time the
    thread was not executing inside the stage. On a thread that shares
    the interpreter with others that is the wait for the interpreter
    lock; around a device call or a pull it also holds what the runtime
    made the caller wait for; a page fault is system CPU and never
    wait. The difference is SIGNED: where the kernel counts a thread's
    CPU time in ticks longer than the stage (10 ms on the chip's host)
    one observation is −tick or +wall and only the mean over many is
    the wait, so a clamp at 0 would bias it. The read is a system call
    with the interpreter lock held (0.4 us on a plain kernel; 6-12 us
    alone on the chip's host, and a served relay lost 0.1 % of its
    rate for each read a pass there), so `cpu=False` is for a stage
    whose wait nothing reads: a parent that its tiles cover, a leg that
    fires per request on many threads, a part that only adds up.

    `then(name)` is a SEAM: one read of each clock closes the running
    stage and opens the next under the new name, so consecutive stages
    tile their parent with no gap and no overlap, in wall time and in
    CPU time (the engine pass's `pass_*` children). `start`/`stop` are
    the explicit edges for an interval that opens in one function and
    closes in another on the SAME thread (`pass_respond`: engine →
    scheduler); `stop` on a stage that is not running is a no-op, so a
    `finally` may always call it. Runtime seam names passed here are
    NOT `STAGES` entries (that is the ablation registry the baseline
    digest pins); an unregistered name is unpriced and never
    flagged."""

    __slots__ = ("name", "rows", "nbytes", "cpu", "seconds", "wait", "_at",
                 "_running", "_annotation", "_ctx")

    def __init__(self, name: str, rows: int = 0, nbytes: int = 0,
                 cpu: bool = True):
        self.name = name
        self.rows = rows
        self.nbytes = nbytes
        self.cpu = cpu  # read time.thread_time at the edges
        self.seconds = 0.0  # of the last closed interval
        self.wait: Optional[float] = None  # None: no CPU clock was read
        self._running = False

    def start(self) -> "stage":
        self._annotation = _log.open_annotation(self.name, "evolu/")
        return self._open(_edge(self.cpu))

    def _open(self, at) -> "stage":
        self._ctx = trace.current()
        self._at = at
        self._running = True
        return self

    def stop(self) -> None:
        if self._running:
            self._close(_edge(self.cpu))

    def then(self, name: str, rows: int = 0, nbytes: int = 0) -> None:
        at = _edge(self.cpu)  # the seam is ONE instant: no gap between tiles
        if self._running:
            self._close(at)
        self.name, self.rows, self.nbytes = name, rows, nbytes
        self._annotation = _log.open_annotation(name, "evolu/")
        self._open(at)

    def _close(self, at) -> None:
        self._running = False
        _log.close_annotation(self._annotation)
        if at is not None and self._at is not None:
            self.seconds = at[0] - self._at[0]
            if self.cpu:
                self.wait = self.seconds - (at[1] - self._at[1])
            self._record(self.seconds, self.wait)

    def _record(self, seconds: float, wait: Optional[float]) -> None:
        """Where a closed interval goes. A subclass that fires per
        request overrides this with a plain histogram observation."""
        _acct.record(self.name, seconds, rows=self.rows,
                     nbytes=self.nbytes, wait=wait)
        if self._ctx is not None:
            trace.record_span(self.name, self._ctx, time.time() - seconds,
                              seconds * 1e3)

    __enter__ = start

    def __exit__(self, *_exc) -> None:
        self.stop()


class batched_stage(stage):
    """`stage` for a hot path that closes several intervals a unit of
    work: its clocks and `evolu/<name>` annotation, but each closed
    interval is kept in `closed` as plain histogram observations,
    `family{stage=<name>}` and (where the CPU clock is read) its wait
    under the family's `_wait_ms` name, for the owner to post in ONE
    `metrics.observe_many` when the unit ends. It skips the
    accountant's totals, fit and gauges and the trace ring, and takes
    the registry lock once a unit, not five times an interval."""

    __slots__ = ("closed",)
    family = "evolu_stage_ms"

    def __init__(self, name: str, closed: Optional[list] = None,
                 cpu: bool = True):
        super().__init__(name, cpu=cpu)
        self.closed = [] if closed is None else closed

    def _record(self, seconds: float, wait: Optional[float]) -> None:
        labels = {"stage": self.name}
        self.closed.append((self.family, seconds * 1e3, labels))
        if wait is not None:
            self.closed.append((self.family[:-2] + "wait_ms", wait * 1e3, labels))


class summed_stage(batched_stage):
    """A stage that may open and close several times a unit of work (a
    `part` of a tile, below; the respond leg's two halves, once a
    request of a pass): its intervals add up in `ms` and go to the
    registry as ONE observation, `observation()`, which the owner posts
    when the unit ends."""

    __slots__ = ("ms",)

    def __init__(self, name: str):
        super().__init__(name, cpu=False)  # its intervals only add up: no wait
        self.ms = 0.0

    def _record(self, seconds: float, wait: Optional[float]) -> None:
        self.ms += seconds * 1e3

    def observation(self) -> tuple:
        return (self.family, self.ms, {"stage": self.name})


_tiled = threading.local()  # .tiles: the `tiles` open on this thread
_NO_PART = contextlib.nullcontext()


class tiles:
    """One command on ONE thread, tiled by stages whose seams lie in
    other modules:

        with anatomy.tiles("recv_", whole="handle", first="clock"):
            ...                          # any depth, any module:
            anatomy.seam("plan_host")    # recv_clock ends, recv_plan_host begins

    `<prefix><whole>` brackets the block; `<prefix><first>` opens with
    it and every `seam(name)` reached on this thread inside the block
    is a `stage.then`: one clock read closes the running tile and opens
    `<prefix><name>`, so the tiles never overlap and sum to the whole
    less its first and last instants. All of them are `batched_stage`s
    posted in one `metrics.observe_many` at the exit. Outside such a
    block `seam` is one thread-local read, so code shared with other
    commands (the planners a Send runs too) names its seams
    unconditionally.

    `part(name)` is a CHILD of whatever tile is running: a `with` block
    (same thread, any module) whose time stays inside its tile, so the
    tiles still sum to the whole. A part reached several times in one
    command (the tree fold: the delta decode in the planner, then the
    fold after the SQLite apply) adds its intervals up and is observed
    once, in the same `observe_many`. Outside a `tiles` block it is a
    shared no-op context."""

    __slots__ = ("prefix", "_whole", "_tile", "_outer", "_parts")

    def __init__(self, prefix: str, whole: str, first: str):
        self.prefix = prefix
        self._whole = batched_stage(prefix + whole)
        self._tile = batched_stage(prefix + first, self._whole.closed)
        self._parts = {}

    def __enter__(self) -> "tiles":
        self._outer = getattr(_tiled, "tiles", None)
        _tiled.tiles = self
        self._whole.start()
        self._tile.start()
        return self

    def __exit__(self, *_exc) -> None:
        self._tile.stop()
        self._whole.stop()
        _tiled.tiles = self._outer
        closed = self._whole.closed
        closed.extend(p.observation() for p in self._parts.values())
        metrics.observe_many(closed)


def seam(name: str) -> None:
    """Close this thread's running tile and open `<prefix><name>`; a
    no-op where no `tiles` block is open."""
    open_tiles = getattr(_tiled, "tiles", None)
    if open_tiles is not None:
        open_tiles._tile.then(open_tiles.prefix + name)


def part(name: str):
    """→ a context manager: `<prefix><name>` as a child of this
    thread's running tile (`tiles`), a shared no-op where none is
    open."""
    open_tiles = getattr(_tiled, "tiles", None)
    if open_tiles is None:
        return _NO_PART
    name = open_tiles.prefix + name
    child = open_tiles._parts.get(name)
    if child is None:
        child = open_tiles._parts[name] = summed_stage(name)
    return child


def record_span(target: str, ms: float, rows: object = 0) -> None:
    """Fold a kernel:* log span into the family (utils/log.py span
    close). Stage label = the span target; rows from the span's n=
    field when present, so the per-target fit separates the fixed
    intercept from the slope exactly like the explicit seams."""
    n = rows if isinstance(rows, int) and rows > 0 else 0
    _acct.record(target, ms / 1e3, rows=n)


def stages_payload() -> dict:
    """The GET /stats "stages" section: per-stage counts, EWMA, fit,
    floor, over-floor tally, and runtime shares."""
    return _acct.payload()


def reset() -> None:
    """Clear accumulators (test isolation via logger.clear()); the
    device kind survives — it is a process property, not a statistic."""
    _acct.reset()
