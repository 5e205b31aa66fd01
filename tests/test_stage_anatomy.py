"""Stage-anatomy plane (evolu_tpu/obs/anatomy.py + the ablation
harness benchmarks/stage_anatomy.py) — registry shape and digest
stability, floor pricing against the recorded v5e laws, unmeasured laws
reading "not measured", an unknown device asked for by name raising,
the evolu_stage_* metrics family
(histograms/counters/gauges, over-floor flagging past warmup, the
decayed slope/fixed fit recovering a synthetic cost law, runtime share
gauges), kernel-span folding through utils.log.span, the /stats
payload, and registry↔harness agreement (variant arity, device-stage
order, truncated-variant structural containment)."""

import json
import os
import sys

import pytest

from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.utils.log import logger, span

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))


@pytest.fixture(autouse=True)
def _clean_slate():
    logger.clear()  # resets metrics registry + anatomy accumulators
    prev = anatomy.get_device_kind()
    yield
    anatomy.set_device_kind(prev)
    logger.configure(False)
    logger.clear()


# --- registry shape + digests ---


def test_registry_shape():
    names = [s.name for s in anatomy.STAGES]
    assert names == [
        "key_sort", "plan_compare", "hash_render", "minute_fold",
        "delta_encode", "pull_wave", "device_dispatch", "host_apply",
    ]
    device = [s for s in anatomy.STAGES if s.kind == "device"]
    assert len(device) == 5
    # Device stages chain: each stage's inputs come from prior outputs
    # or the kernel's own input columns.
    produced = {"cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix"}
    for s in device:
        assert set(s.inputs) <= produced, (s.name, s.inputs)
        produced |= set(s.outputs)
    # Every price term names a law key some device has measured (a
    # typo would silently unprice the stage everywhere), and every law
    # a device records prices some stage.
    price_keys = {
        law_key for s in anatomy.STAGES for law_key, unit in s.price
        if unit != "device_pipeline"
    }
    measured = set().union(*anatomy.COST_LAWS.values())
    assert price_keys == measured


def test_registry_digest_is_stable_and_law_sensitive():
    d1 = anatomy.registry_digest()
    assert d1 == anatomy.registry_digest()
    assert len(d1) == 8 and int(d1, 16) >= 0
    old = anatomy.COST_LAWS[anatomy.V5E]["sort_key_ms_per_1m"]
    try:
        anatomy.COST_LAWS[anatomy.V5E]["sort_key_ms_per_1m"] = old * 2
        assert anatomy.registry_digest() != d1  # re-pricing moves the gate
    finally:
        anatomy.COST_LAWS[anatomy.V5E]["sort_key_ms_per_1m"] = old


# --- floor pricing ---


def test_floor_prices_v5e_laws_exactly():
    v5e = anatomy.V5E
    # key_sort at 1M rows = 1.5 (key) + 2 × 0.75 (payloads) = 3.0 ms.
    assert anatomy.floor_ms("key_sort", rows=1_000_000,
                            device_kind=v5e) == pytest.approx(3.0)
    # host_apply is throughput-priced: 720k rows at 720k rows/s = 1 s.
    assert anatomy.floor_ms("host_apply", rows=720_000,
                            device_kind=v5e) == pytest.approx(1000.0)
    # Span targets price as the sum of their mapped stages.
    merkle = sum(
        anatomy.floor_ms(s, rows=1_000_000, device_kind=v5e)
        for s in ("hash_render", "minute_fold", "delta_encode")
    )
    assert anatomy.floor_ms("kernel:merkle", rows=1_000_000,
                            device_kind=v5e) == pytest.approx(merkle)


def test_floor_prices_bandwidth_and_fixed_terms_where_measured():
    # pull_wave is bandwidth-priced: 7650 MB at 7650 MB/s = 1000 ms.
    assert anatomy.floor_ms("pull_wave", nbytes=7_650_000_000,
                            device_kind="cpu") == pytest.approx(1000.0)
    # device_dispatch = fixed intercept + the whole device pipeline.
    dev_sum = sum(
        anatomy.floor_ms(s.name, rows=1_000_000, device_kind="cpu")
        for s in anatomy.STAGES if s.kind == "device"
    )
    assert anatomy.floor_ms("device_dispatch", rows=1_000_000,
                            device_kind="cpu") == pytest.approx(261.0 + dev_sum)


def test_unmeasured_v5e_laws_read_not_measured():
    """No dispatch intercept and no pull bandwidth has been measured on
    the attached chip: the stages priced by them are unpriced there —
    None, never a number carried over from another attachment — and so
    is everything that sums them."""
    laws = anatomy.COST_LAWS[anatomy.V5E]
    assert "fixed_dispatch_ms" not in laws and "pull_mb_per_s" not in laws
    assert anatomy.floor_ms("pull_wave", nbytes=1 << 20,
                            device_kind=anatomy.V5E) is None
    assert anatomy.floor_ms("device_dispatch", rows=1 << 20,
                            device_kind=anatomy.V5E) is None
    assert anatomy.floor_ms("kernel:reconcile", rows=1 << 20,
                            device_kind=anatomy.V5E) is None
    anatomy.set_device_kind(anatomy.V5E)
    for _ in range(4):  # past warmup: unpriced is never flagged
        anatomy.record_stage("pull_wave", 10.0, nbytes=1)
    assert metrics.get_counter("evolu_stage_over_floor_total",
                               stage="pull_wave") == 0
    assert metrics.registry.get_gauge("evolu_stage_floor_ms",
                                      stage="pull_wave") is None
    assert anatomy.stages_payload()["stages"]["pull_wave"]["floor_ms"] is None


def test_unknown_device_by_name_raises_and_unknown_stage_is_unpriced():
    with pytest.raises(KeyError):
        anatomy.floor_ms("key_sort", rows=1 << 20, device_kind="riscv")
    assert anatomy.floor_ms("no_such_stage", rows=1 << 20,
                            device_kind=anatomy.V5E) is None
    # The runtime accountant names no device: whatever the process came
    # up on is recorded unpriced, and compute is never gated.
    anatomy.set_device_kind("riscv")
    assert anatomy.floor_ms("key_sort", rows=1 << 20) is None
    anatomy.record_stage("key_sort", 0.5, rows=1 << 20)
    assert anatomy.stages_payload()["stages"]["key_sort"]["floor_ms"] is None


# --- the evolu_stage_* family ---


def test_record_stage_emits_family():
    anatomy.set_device_kind(anatomy.V5E)
    anatomy.record_stage("host_apply", 0.010, rows=7200)  # floor = 10 ms
    assert metrics.get_counter("evolu_stage_seconds_total",
                               stage="host_apply") == pytest.approx(0.010)
    assert metrics.get_counter("evolu_stage_rows_total",
                               stage="host_apply") == 7200
    _, _, _, count = metrics.registry.get_histogram("evolu_stage_ms",
                                                    stage="host_apply")
    assert count == 1
    assert metrics.registry.get_gauge(
        "evolu_stage_floor_ms", stage="host_apply") == pytest.approx(10.0)
    assert metrics.registry.get_gauge(
        "evolu_stage_over_floor_ratio", stage="host_apply"
    ) == pytest.approx(1.0)


def test_over_floor_flags_only_past_warmup():
    anatomy.set_device_kind(anatomy.V5E)
    # floor = 10 ms; 100 ms is 10× over FLOOR_FACTOR=4.
    for _ in range(2):  # warmup records never flag (compile time)
        anatomy.record_stage("host_apply", 0.100, rows=7200)
    assert metrics.get_counter("evolu_stage_over_floor_total",
                               stage="host_apply") == 0
    anatomy.record_stage("host_apply", 0.100, rows=7200)
    assert metrics.get_counter("evolu_stage_over_floor_total",
                               stage="host_apply") == 1
    anatomy.record_stage("host_apply", 0.011, rows=7200)  # healthy: no flag
    assert metrics.get_counter("evolu_stage_over_floor_total",
                               stage="host_apply") == 1


def test_slope_fit_recovers_synthetic_law():
    # Synthetic stage law: 5 ms fixed + 2 µs/row. The decayed online
    # fit must separate intercept from slope (the wall/count trap).
    anatomy.set_device_kind("unknown-bench")
    for rows in (1000, 4000, 16000, 2000, 8000, 32000):
        anatomy.record_stage("device_dispatch", (5.0 + 0.002 * rows) / 1e3,
                             rows=rows)
    slope = metrics.registry.get_gauge("evolu_stage_slope_ns_per_row",
                                       stage="device_dispatch")
    fixed = metrics.registry.get_gauge("evolu_stage_fixed_ms",
                                       stage="device_dispatch")
    assert slope == pytest.approx(2000.0, rel=0.05)  # 2 µs = 2000 ns/row
    assert fixed == pytest.approx(5.0, rel=0.05)


def test_runtime_share_gauges():
    anatomy.set_device_kind("unknown-bench")
    anatomy.record_stage("device_dispatch", 0.030, rows=100)
    anatomy.record_stage("pull_wave", 0.010, nbytes=1000)
    anatomy.record_stage("host_apply", 0.060, rows=100)
    total = 0.030 + 0.010 + 0.060
    assert metrics.registry.get_gauge(
        "evolu_stage_share", stage="host_apply"
    ) == pytest.approx(0.060 / total)
    assert metrics.registry.get_gauge(
        "evolu_stage_share", stage="pull_wave"
    ) == pytest.approx(0.010 / total)
    payload = anatomy.stages_payload()
    assert payload["stages"]["device_dispatch"]["share"] == pytest.approx(
        0.030 / total)


def test_disabled_registry_records_nothing():
    metrics.set_enabled(False)
    try:
        anatomy.record_stage("host_apply", 0.5, rows=10_000)
    finally:
        metrics.set_enabled(True)
    assert anatomy.stages_payload()["stages"] == {}


def test_stage_primitive_tiles_its_parent_at_the_seams():
    """`stage` is `record_stage` at both edges of an interval; `then`
    is a seam (one clock read closes a tile and opens the next), so the
    children of a parent tile it with no gap and no overlap; `stop` on
    a stage that is not running, or was never started, is a no-op."""
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
    assert stages["pass_pack"]["floor_ms"] is None  # runtime seams are unpriced
    children = sum(metrics.get_counter("evolu_stage_seconds_total", stage=s)
                   for s in ("pass_pack", "pass_parse", "pass_layout"))
    assert 0 < children <= whole.seconds
    assert children == pytest.approx(whole.seconds, abs=2e-4)
    assert metrics.get_counter("evolu_stage_rows_total", stage="pass_pack") == 7
    assert metrics.get_counter("evolu_stage_bytes_total", stage="pass_parse") == 64
    # The seam names are runtime names, not ablation-registry entries.
    assert not {"pass_pack", "pass_parse", "pass_layout"} & {
        s.name for s in anatomy.STAGES}


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
    """`_StageAccountant.record`: `evolu_stage_ms`, its wait, the shard
    split and the seconds / rows / bytes totals under ONE acquisition
    of the registry's lock (three to five before ISSUE 37); an unpriced
    stage's first record sets no gauge, so one is all it takes."""
    anatomy.set_device_kind("unknown-bench")
    lock = _CountingLock(metrics.registry._lock)
    monkeypatch.setattr(metrics.registry, "_lock", lock)
    anatomy._acct.record("pass_x37", 0.004, rows=7, nbytes=64, shard=2, wait=0.001)
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


def test_kernel_span_folds_into_family():
    anatomy.set_device_kind(anatomy.V5E)
    with span("kernel:merkle", "t", n=1000):
        pass
    with span("host:apply", "t"):  # non-kernel spans stay out
        pass
    payload = anatomy.stages_payload()
    assert payload["stages"]["kernel:merkle"]["count"] == 1
    assert "host:apply" not in payload["stages"]
    assert metrics.get_counter("evolu_stage_rows_total",
                               stage="kernel:merkle") == 1000
    # The span target priced via its mapped stages.
    assert payload["stages"]["kernel:merkle"]["floor_ms"] == pytest.approx(
        anatomy.floor_ms("kernel:merkle", rows=1000, device_kind=anatomy.V5E))


def test_stages_payload_shape_and_reset():
    anatomy.set_device_kind(anatomy.V5E)
    anatomy.record_stage("host_apply", 0.010, rows=7200)
    p = anatomy.stages_payload()
    assert p["device_kind"] == anatomy.V5E
    assert p["registry_digest"] == anatomy.registry_digest()
    assert p["floor_factor"] == anatomy.FLOOR_FACTOR
    st = p["stages"]["host_apply"]
    assert st["count"] == 1
    assert st["ewma_ms"] == pytest.approx(10.0)
    json.dumps(p)  # must be JSON-clean for GET /stats
    logger.clear()
    assert anatomy.stages_payload()["stages"] == {}
    assert anatomy.get_device_kind() == anatomy.V5E  # survives clear


# --- registry ↔ ablation-harness agreement ---


def test_harness_matches_registry():
    import stage_anatomy as sa

    assert sa.DEVICE_STAGES == tuple(
        s.name for s in anatomy.STAGES if s.kind == "device")
    # Cumulative arity: key_sort 3, +3, +2, +5, +3 = 16.
    assert [sa.variant_arity(s) for s in sa.DEVICE_STAGES] == [3, 6, 8, 13, 16]
    assert list(sa.stage_output_indices("hash_render")) == [6, 7]
    assert list(sa.stage_output_indices("key_sort")) == [0, 1, 2]


def test_truncated_variants_nest_structurally():
    """Each truncated variant's jaxpr primitive multiset must be a
    sub-multiset of the next one's — ablation only ever REMOVES tail
    work, so a stage can never change the upstream computation it
    claims to be measuring."""
    jax = pytest.importorskip("jax")
    import numpy as np

    import stage_anatomy as sa

    n = 256
    probe = (
        np.full(n, 0x7FFFFFFF, np.int32),
        np.zeros(n, np.uint64), np.zeros(n, np.uint64),
        np.zeros(n, np.uint64), np.zeros(n, np.uint64),
        np.zeros(n, np.int64),
    )
    from collections import Counter

    from evolu_tpu.parallel.mesh import create_mesh

    mesh = create_mesh()
    multisets = []
    with jax.enable_x64(True):
        for name in sa.DEVICE_STAGES:
            loop = sa.make_variant_loop(mesh, 1, sa.build_variant(name))
            jaxpr = jax.make_jaxpr(loop)(*probe)
            prims = []
            sa._collect_prims(jaxpr.jaxpr, prims)
            multisets.append(Counter(prims))
    for prev, cur in zip(multisets, multisets[1:]):
        assert not prev - cur, f"ablation removed upstream work: {prev - cur}"
    # And each stage genuinely adds primitives.
    for prev, cur in zip(multisets, multisets[1:]):
        assert cur - prev


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
