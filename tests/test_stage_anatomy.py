"""The stage primitive (evolu_tpu/obs/anatomy.py): the evolu_stage_*
histograms and totals a record posts in one registry acquisition and
nothing beside them, seams that tile their parent, a stage's CPU time
beside its wall time, kernel-span folding through utils.log.span, and
the /stats payload as a view of the registry."""

import json

import pytest

from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.utils.log import logger, span


@pytest.fixture(autouse=True)
def _clean_slate():
    logger.clear()  # resets the metrics registry, which the payload reads
    prev = anatomy.get_device_kind()
    yield
    anatomy.set_device_kind(prev)
    logger.configure(False)
    logger.clear()


# --- the evolu_stage_* family ---


def test_record_stage_emits_family():
    anatomy.record_stage("host_apply", 0.010, rows=7200)
    assert metrics.get_counter("evolu_stage_seconds_total",
                               stage="host_apply") == pytest.approx(0.010)
    assert metrics.get_counter("evolu_stage_rows_total",
                               stage="host_apply") == 7200
    _, _, total, count = metrics.registry.get_histogram("evolu_stage_ms",
                                                        stage="host_apply")
    assert (total, count) == (pytest.approx(10.0), 1)


def test_disabled_registry_records_nothing():
    metrics.set_enabled(False)
    try:
        anatomy.record_stage("host_apply", 0.5, rows=10_000)
    finally:
        metrics.set_enabled(True)
    assert anatomy.stages_payload()["stages"] == {}
    assert metrics.registry.get_histogram("evolu_stage_ms", stage="host_apply") is None


def test_stage_primitive_tiles_its_parent_at_the_seams(monkeypatch):
    """`stage` is `record_stage` at both edges of an interval; `then`
    is a seam (one clock read closes a tile and opens the next), so the
    children of a parent tile it with no gap and no overlap; `stop` on
    a stage that is not running, or was never started, is a no-op. On
    a clock that moves one step a read (a power of two, so every sum is
    exact) the three children are the parent less its first and last
    instants, to the bit."""
    step = 2.0 ** -7
    monkeypatch.setattr(anatomy, "time", _Clocks(wall_step=step, cpu_step=2.0 ** -9))
    with anatomy.stage("device_dispatch", rows=7) as whole, \
            anatomy.stage("pass_pack", rows=7) as tile:
        tile.then("pass_parse")
        tile.nbytes = 64  # known only inside the interval
        tile.then("pass_layout")
    tile.stop()
    anatomy.stage("pass_respond").stop()  # never started
    stages = anatomy.stages_payload()["stages"]
    assert [stages[s]["count"] for s in (
        "device_dispatch", "pass_pack", "pass_parse", "pass_layout")] == [1, 1, 1, 1]
    assert "pass_respond" not in stages
    children = sum(metrics.get_counter("evolu_stage_seconds_total", stage=s)
                   for s in ("pass_pack", "pass_parse", "pass_layout"))
    assert 0 < children <= whole.seconds
    assert children == 3 * step and whole.seconds == 5 * step
    assert children == whole.seconds - 2 * step  # no gap, no overlap at a seam
    assert metrics.get_counter("evolu_stage_rows_total", stage="pass_pack") == 7
    assert metrics.get_counter("evolu_stage_bytes_total", stage="pass_parse") == 64


def test_stage_lands_in_the_ambient_trace_and_costs_nothing_disabled():
    from evolu_tpu.obs import trace

    root = trace.start_span("engine.batch")
    with trace.use(root.context), anatomy.stage("pass_tree"):
        pass
    root.end()
    (span_,) = [s for s in trace.spans_for(root.trace_id) if s.name == "pass_tree"]
    assert span_.parent_id == root.context.span_id and span_.duration_ms >= 0
    with anatomy.stage("pass_tree"):  # no ambient context: registry only
        pass
    assert len([s for s in trace.recorder.dump() if s.name == "pass_tree"]) == 1
    metrics.set_enabled(False)
    try:
        with anatomy.stage("pass_insert"):
            pass
    finally:
        metrics.set_enabled(True)
    assert "pass_insert" not in anatomy.stages_payload()["stages"]


# --- a stage's CPU time beside its wall time (ISSUE 37) ---


class _Clocks:
    """Stands in for the `time` module inside obs.anatomy: every read
    is counted and advances its clock by a fixed step."""

    def __init__(self, wall_step=0.010, cpu_step=0.004):
        self.steps = {"perf_counter": wall_step, "thread_time": cpu_step}
        self.now = {"perf_counter": 100.0, "thread_time": 5.0}
        self.reads = {"perf_counter": 0, "thread_time": 0}

    def _read(self, clock):
        self.reads[clock] += 1
        self.now[clock] += self.steps[clock]
        return self.now[clock]

    def perf_counter(self):
        return self._read("perf_counter")

    def thread_time(self):
        return self._read("thread_time")

    def time(self):
        return 1_700_000_000.0


def _ms(family, stage):
    h = metrics.registry.get_histogram(family, stage=stage)
    return (h[2], h[3]) if h else (0.0, 0)  # (sum, count)


def test_a_stage_that_sleeps_then_spins_waits_for_the_sleep():
    """wait = wall - this thread's CPU: the sleep is wait, the spin is
    not; loose enough for a busy machine, where a descheduled spin only
    adds to both sides of `wait <= wall`."""
    import time

    with anatomy.stage("sleep_spin") as st:
        time.sleep(0.08)
        spun = time.thread_time() + 0.02
        while time.thread_time() < spun:
            pass
    assert 0.0 <= st.wait <= st.seconds
    assert st.wait >= 0.75 * 0.08
    assert st.seconds - st.wait >= 0.019  # the spin's CPU is never wait
    assert _ms("evolu_stage_ms", "sleep_spin") == (pytest.approx(st.seconds * 1e3), 1)
    assert _ms("evolu_stage_wait_ms", "sleep_spin") == (pytest.approx(st.wait * 1e3), 1)
    # an interval known only afterwards has no CPU reading: no wait
    anatomy.record_stage("host_apply", 0.010, rows=10, shard=3)
    assert _ms("evolu_stage_ms", "host_apply")[1] == 1
    assert _ms("evolu_stage_wait_ms", "host_apply") == (0.0, 0)


def test_a_seam_is_one_read_of_each_clock_and_cpu_tiles_as_wall_does(monkeypatch):
    clocks = _Clocks()
    monkeypatch.setattr(anatomy, "time", clocks)
    with anatomy.stage("whole") as whole, anatomy.stage("tile_a") as tile:
        tile.then("tile_b")
        tile.then("tile_c")
    # two starts, two seams, two stops: six instants, one read of each clock each
    assert clocks.reads == {"perf_counter": 6, "thread_time": 6}
    tiles = ("tile_a", "tile_b", "tile_c")
    for s in tiles:  # each tile: 10 ms of wall, 4 ms of CPU
        assert _ms("evolu_stage_ms", s) == (pytest.approx(10.0), 1)
        assert _ms("evolu_stage_wait_ms", s) == (pytest.approx(6.0), 1)
    # no gap at a seam on either clock: the tiles' CPU is the whole's
    # less its first and last instants, as their wall time is
    cpu = sum(_ms("evolu_stage_ms", s)[0] - _ms("evolu_stage_wait_ms", s)[0] for s in tiles)
    assert cpu == pytest.approx(3 * 4.0)
    assert whole.seconds == pytest.approx(0.050) and whole.wait == pytest.approx(0.030)
    assert (whole.seconds - whole.wait) * 1e3 == pytest.approx(cpu + 2 * 4.0)
    # A CPU clock that ticks coarser than the stage lasts (10 ms on the
    # chip's host) reads more CPU than wall in one interval and none in
    # the next: the difference is kept SIGNED, so that the mean over
    # many is the wait; clamped at 0 it would read 1.0 here, not 0.0.
    monkeypatch.setattr(anatomy, "time", _Clocks(wall_step=0.001, cpu_step=0.002))
    with anatomy.stage("tick") as st:
        pass
    assert st.wait == pytest.approx(-0.001)
    monkeypatch.setattr(anatomy, "time", _Clocks(wall_step=0.001, cpu_step=0.0))
    with anatomy.stage("tick"):
        pass
    assert _ms("evolu_stage_wait_ms", "tick") == (pytest.approx(0.0, abs=1e-9), 2)


def test_a_batched_stage_keeps_its_wait_under_its_familys_name(monkeypatch):
    clocks = _Clocks()
    monkeypatch.setattr(anatomy, "time", clocks)

    class leg(anatomy.batched_stage):
        __slots__ = ()
        family = "evolu_test_leg_ms"

    tile = leg("first").start()
    tile.then("second")
    tile.stop()
    assert [(f, round(v, 6), labels) for f, v, labels in tile.closed] == [
        ("evolu_test_leg_ms", 10.0, {"stage": "first"}),
        ("evolu_test_leg_wait_ms", 6.0, {"stage": "first"}),
        ("evolu_test_leg_ms", 10.0, {"stage": "second"}),
        ("evolu_test_leg_wait_ms", 6.0, {"stage": "second"})]
    with anatomy.tiles("t37_", whole="handle", first="one"):
        anatomy.seam("two")
        with anatomy.part("child"):
            pass
    for s in ("t37_handle", "t37_one", "t37_two"):
        assert _ms("evolu_stage_ms", s)[1] == _ms("evolu_stage_wait_ms", s)[1] == 1
    assert _ms("evolu_stage_ms", "t37_child")[1] == 1
    # a part records no wait and reads no CPU clock: three instants the
    # leg, four the tiles (two starts, a seam, two stops share... five)
    assert _ms("evolu_stage_wait_ms", "t37_child") == (0.0, 0)
    assert clocks.reads == {"perf_counter": 3 + 5 + 2, "thread_time": 3 + 5}

    tile = leg("first", cpu=False).start()  # a stage whose wait nothing reads
    tile.then("second")
    tile.stop()
    assert [(f, labels) for f, _v, labels in tile.closed] == [
        ("evolu_test_leg_ms", {"stage": "first"}), ("evolu_test_leg_ms", {"stage": "second"})]
    assert tile.wait is None and clocks.reads["thread_time"] == 3 + 5


def test_a_disabled_registry_reads_no_clock_at_all(monkeypatch):
    clocks = _Clocks()
    monkeypatch.setattr(anatomy, "time", clocks)
    metrics.set_enabled(False)
    try:
        with anatomy.stage("pass_pack") as tile:
            tile.then("pass_parse")
        leg = anatomy.batched_stage("leg").start()
        leg.then("next")
        leg.stop()
        with anatomy.tiles("t37_", whole="handle", first="one"):
            anatomy.seam("two")
            with anatomy.part("child"):
                pass
    finally:
        metrics.set_enabled(True)
    assert clocks.reads == {"perf_counter": 0, "thread_time": 0}
    assert leg.closed == [] and tile.seconds == 0.0 and tile.wait is None
    assert anatomy.stages_payload()["stages"] == {}
    # enabled between a start and its stop: an interval with one edge goes nowhere
    metrics.set_enabled(False)
    half = anatomy.stage("half").start()
    metrics.set_enabled(True)
    half.stop()
    assert "half" not in anatomy.stages_payload()["stages"]


class _CountingLock:
    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_stage_posts_its_histograms_and_totals_in_one_acquisition(monkeypatch):
    """`anatomy.record_stage`: `evolu_stage_ms`, its wait, the shard
    split and the seconds / rows / bytes totals under ONE acquisition of
    the registry's lock, and nothing else."""
    lock = _CountingLock(metrics.registry._lock)
    monkeypatch.setattr(metrics.registry, "_lock", lock)
    anatomy.record_stage("pass_x37", 0.004, rows=7, nbytes=64, shard=2,
                         wait=0.001)
    assert lock.taken == 1
    with anatomy.stage("pass_y37", rows=3):
        pass
    assert lock.taken == 2
    monkeypatch.undo()
    assert _ms("evolu_stage_ms", "pass_x37") == (pytest.approx(4.0), 1)
    assert _ms("evolu_stage_wait_ms", "pass_x37") == (pytest.approx(1.0), 1)
    shard = metrics.registry.get_histogram("evolu_stage_shard_ms", stage="pass_x37", shard="2")
    assert (shard[2], shard[3]) == (pytest.approx(4.0), 1)
    assert metrics.get_counter("evolu_stage_seconds_total", stage="pass_x37") == pytest.approx(0.004)
    assert metrics.get_counter("evolu_stage_rows_total", stage="pass_x37") == 7
    assert metrics.get_counter("evolu_stage_bytes_total", stage="pass_x37") == 64
    assert metrics.get_counter("evolu_stage_rows_total", stage="pass_y37") == 3
    assert metrics.get_counter("evolu_stage_bytes_total", stage="pass_y37") == 0


@pytest.mark.parametrize("via", ["stage", "stage.then", "record_stage", "record_span"])
def test_a_record_posts_once_and_sets_no_gauge(via, monkeypatch):
    """Every way into the family is ONE `metrics.observe_many` an
    interval, carrying its histogram(s) and the three totals, and no
    gauge and no separate counter beside it."""
    def refuse(*_a, **_k):
        raise AssertionError("a stage record set a gauge or a lone counter")

    for target in (metrics, metrics.registry):
        monkeypatch.setattr(target, "set_gauge", refuse)
        monkeypatch.setattr(target, "inc", refuse)
    posts = []
    real = metrics.observe_many
    monkeypatch.setattr(metrics, "observe_many",
                        lambda items, **kw: posts.append((list(items), kw)) or real(items, **kw))
    if via == "stage":
        with anatomy.stage("seam_a", rows=3, nbytes=8):
            pass
    elif via == "stage.then":
        tile = anatomy.stage("seam_a", rows=3, nbytes=8).start()
        tile.then("seam_b")
        assert len(posts) == 1  # the seam closed one interval: one post
        tile.stop()
        posts = posts[:1]
    elif via == "record_stage":
        anatomy.record_stage("seam_a", 0.002, rows=3, nbytes=8)
    else:
        anatomy.record_span("seam_a", 2.0, rows=3)
    ((items, kw),) = posts
    families = [f for f, _v, labels in items if labels == {"stage": "seam_a"}]
    assert families == (["evolu_stage_ms", "evolu_stage_wait_ms"]
                        if via.startswith("stage") else ["evolu_stage_ms"])
    assert [(f, labels) for f, _v, labels in kw["also_inc"]] == [
        (f"evolu_stage_{t}_total", {"stage": "seam_a"}) for t in ("seconds", "rows", "bytes")]
    assert metrics.get_counter("evolu_stage_rows_total", stage="seam_a") == 3
    assert metrics.registry._gauges == {}


def test_kernel_span_folds_into_family():
    with span("kernel:merkle", "t", n=1000):
        pass
    with span("host:apply", "t"):  # non-kernel spans stay out
        pass
    payload = anatomy.stages_payload()
    assert payload["stages"]["kernel:merkle"]["count"] == 1
    assert "host:apply" not in payload["stages"]
    assert metrics.get_counter("evolu_stage_rows_total",
                               stage="kernel:merkle") == 1000
    h = metrics.registry.get_histogram("evolu_stage_ms", stage="kernel:merkle")
    assert payload["stages"]["kernel:merkle"]["total_ms"] == h[2]


def test_stages_payload_shape_and_reset():
    anatomy.set_device_kind("TPU v5 lite")
    anatomy.record_stage("host_apply", 0.010, rows=7200)
    anatomy.record_stage("host_apply", 0.030, rows=7200)
    p = anatomy.stages_payload()
    assert set(p) == {"device_kind", "stages"}
    assert p["device_kind"] == "TPU v5 lite"
    assert p["stages"]["host_apply"] == {
        "count": 2, "total_ms": pytest.approx(40.0), "mean_ms": pytest.approx(20.0)}
    json.dumps(p)  # must be JSON-clean for GET /stats
    logger.clear()
    assert anatomy.stages_payload()["stages"] == {}
    assert anatomy.get_device_kind() == "TPU v5 lite"  # survives clear


# --- the client tree's counters (ISSUE 34) ---


@pytest.mark.parametrize("route", ["arrays", "keys"])
def test_fold_counts_the_nodes_it_copied_in_the_acquisition_it_had(route, monkeypatch):
    """`evolu_merkle_fold_nodes_total` and `_route_total{route}` ride
    `_fold_tree`'s one `inc_many` with the minutes and the calls, on both
    routes of the deltas: a tree that is text folds as text, a dict by
    the dict route, and each writes the batch's distinct nodes."""
    import numpy as np

    from evolu_tpu.core.merkle import MerkleText, MinuteDeltas, minutes_base3
    from evolu_tpu.storage.apply import _fold_tree

    posts = []
    real = metrics.inc_many
    monkeypatch.setattr(metrics, "inc_many", lambda items: posts.append(1) or real(items))
    minutes = np.arange(28_333_334, 28_333_334 + 40, dtype=np.int64)  # 2023, 16-digit keys
    deltas = MinuteDeltas(minutes, np.arange(40, dtype=np.int32) + 1)
    keys = {minutes_base3(int(m) * 60_000) for m in minutes}
    if route == "keys":
        deltas = dict(deltas)
    want = 1 + len({k[:i] for k in keys for i in range(1, 17)})  # each distinct node
    text = _fold_tree(MerkleText("{}"), deltas)
    assert posts == [1] and isinstance(text, MerkleText)
    plain = _fold_tree({}, deltas)
    assert posts == [1, 1] and type(plain) is dict and plain == text
    assert metrics.get_counter("evolu_merkle_fold_nodes_total") == 2 * want
    assert metrics.get_counter("evolu_merkle_fold_minutes_total") == 2 * 40
    assert metrics.get_counter("evolu_merkle_fold_calls_total") == 2
    assert metrics.get_counter("evolu_merkle_fold_route_total", route="text") == 1
    assert metrics.get_counter("evolu_merkle_fold_route_total", route="dict") == 1


def test_clock_reads_the_tree_as_its_text_and_writes_a_text_as_it_is(monkeypatch):
    """`read_clock` hands `__clock.merkleTree` back as a `MerkleText` and
    parses nothing; `update_clock` writes a text as it is and dumps any
    other tree in key order; the bytes counter counts both legs. A
    rolled-back write leaves `__clock`, and so the next read, as it was."""
    import json

    from evolu_tpu.core.merkle import MerkleText, fold_key_deltas
    from evolu_tpu.core.types import CrdtClock
    from evolu_tpu.storage.clock import read_clock, update_clock
    from evolu_tpu.storage.native import open_database
    from evolu_tpu.storage.schema import init_db_model

    db = open_database(backend="python")
    try:
        init_db_model(db, "zoo zoo zoo zoo zoo zoo zoo zoo zoo zoo zoo wrong")
        parses = []
        real = json.loads
        monkeypatch.setattr(json, "loads", lambda s, *a, **k: parses.append(s) or real(s, *a, **k))
        clock = read_clock(db)
        assert isinstance(clock.merkle_tree, MerkleText) and clock.merkle_tree.text == "{}"
        tree, _ = fold_key_deltas(clock.merkle_tree, {"1220221222001120": 5})
        assert isinstance(tree, MerkleText)
        with pytest.raises(RuntimeError):
            with db.transaction():
                text = update_clock(db, CrdtClock(clock.timestamp, tree))
                assert text is tree.text
                raise RuntimeError("after update_clock")
        assert read_clock(db).merkle_tree.text == "{}"  # rolled back
        text = update_clock(db, CrdtClock(clock.timestamp, tree))
        assert read_clock(db).merkle_tree.text == text
        plain = {"hash": 5, "1": {"hash": 5}}  # a dict is stored in key order as ever
        assert update_clock(db, CrdtClock(clock.timestamp, plain)) == '{"1":{"hash":5},"hash":5}'
        assert read_clock(db).merkle_tree == plain
        assert parses == ['{"1":{"hash":5},"hash":5}']  # the one node read, by `==`
        assert metrics.get_counter("evolu_merkle_tree_bytes_total", leg="load") \
            == 2 + 2 + len(text) + len('{"1":{"hash":5},"hash":5}')
        assert metrics.get_counter("evolu_merkle_tree_bytes_total", leg="store") \
            == 2 * len(text) + len('{"1":{"hash":5},"hash":5}')
    finally:
        db.close()
