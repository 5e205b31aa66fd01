"""Stage timing: one account of where a pass's time goes, kept in the
metrics registry and nowhere else.

`stage` times an interval on the thread that does the work;
`record_stage` takes one known only afterwards (the write-behind drain
workers); `record_span` folds a `kernel:*` log span in. Each closed
interval is ONE `metrics.observe_many`:

- `evolu_stage_ms{stage}`, the histogram every per-stage reading is
  taken from;
- `evolu_stage_wait_ms{stage}`, wall less the thread's own CPU time,
  where `stage` read the CPU clock;
- `evolu_stage_shard_ms{stage, shard}`, where a shard is given;
- `evolu_stage_seconds_total`, `evolu_stage_rows_total` and
  `evolu_stage_bytes_total{stage}`.

`stage` also mirrors the interval into the profiler's trace (an
`evolu/<name>` annotation) and, under an ambient sampled `obs.trace`
context, into the distributed trace. `batched_stage`, `summed_stage`
and `tiles` keep the intervals of a unit of work for one post when it
ends. `stages_payload` is the `GET /stats` view of the
`evolu_stage_ms` histogram.

This module is part of `evolu_tpu.obs` and therefore MUST NOT import
jax (tests/test_import_hygiene.py): the device kind, a label of the
payload and of a captured profile, is pushed in via `set_device_kind`
from the jax side (parallel/mesh.py), and every value recorded here is
a host-side Python float the hot paths already hold. With
`metrics.registry` disabled a record is one attribute read.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

from evolu_tpu.obs import metrics, trace
from evolu_tpu.utils import log as _log  # jax-free at import (annotations load lazily)

_device_kind = "unknown"


def set_device_kind(device_kind: str) -> None:
    """Push the device kind in from the jax side (parallel/mesh.py at
    mesh creation) — this module must never ask jax itself."""
    global _device_kind
    _device_kind = str(device_kind)


def get_device_kind() -> str:
    return _device_kind


def record_stage(stage: str, seconds: float, rows: int = 0,
                 nbytes: int = 0, shard: Optional[int] = None,
                 wait: Optional[float] = None) -> None:
    """One closed interval into the registry: its histograms and its
    totals under ONE acquisition of the registry's lock, on the thread
    that is the relay's bottleneck for most of the stages a pass
    closes. Called directly for an interval known only afterwards (the
    write-behind drain workers, with their shard index); a seam that
    can bracket its work uses `stage`."""
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
    metrics.observe_many(observed, also_inc=(
        ("evolu_stage_seconds_total", seconds, labels),
        ("evolu_stage_rows_total", rows, labels),
        ("evolu_stage_bytes_total", nbytes, labels)))


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
    `finally` may always call it. Any name is a stage: the label is
    the name the seam passes, and nothing else is kept of it."""

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
        record_stage(self.name, seconds, rows=self.rows,
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
    `metrics.observe_many` when the unit ends. It skips the seconds /
    rows / bytes totals and the trace ring, and takes the registry lock
    once a unit, not once an interval."""

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
    field when present."""
    n = rows if isinstance(rows, int) and rows > 0 else 0
    record_stage(target, ms / 1e3, rows=n)


def stages_payload() -> dict:
    """The GET /stats "stages" section: the `evolu_stage_ms` histogram
    read through the registry's snapshot, one entry a `stage` label
    (`count`, `total_ms`, `mean_ms`), and the device kind. It keeps
    nothing of its own: `logger.clear()`'s registry reset empties it."""
    stages = {}
    for series in metrics.snapshot()["histograms"].get("evolu_stage_ms", ()):
        count, total = series["count"], series["sum"]
        stages[series["labels"]["stage"]] = {
            "count": count, "total_ms": total, "mean_ms": total / count}
    return {"device_kind": _device_kind, "stages": stages}
