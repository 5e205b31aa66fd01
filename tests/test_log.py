"""Logging/tracing subsystem (reference log.ts + SURVEY.md §5)."""

from evolu_tpu.core.types import CrdtClock
from evolu_tpu.storage.clock import read_clock, update_clock
from evolu_tpu.storage.schema import init_db_model
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.utils.log import Logger, logger


def test_target_gating():
    lg = Logger(enabled=False)
    lg.log("dev", "hidden")
    assert lg.recent_events() == []
    lg.configure("dev")
    lg.log("dev", "shown")
    lg.log("clock:read", "not this target")
    assert [e.message for e in lg.recent_events()] == ["shown"]
    lg.configure(True)
    lg.log("clock:read", "now everything")
    assert len(lg.recent_events()) == 2


def test_span_records_duration_even_when_disabled():
    lg = Logger(enabled=False)
    with lg.span("kernel:merge", "plan", n=3):
        pass
    stats = lg.duration_stats("kernel:merge")
    assert stats is not None and stats[0] == 1 and stats[1] >= 0
    (ev,) = lg.recent_events("kernel:merge")
    assert ev.duration_ms is not None and ev.fields == {"n": 3}


def test_clock_targets_fire(capsys):
    logger.configure(["clock:read", "clock:update"])
    try:
        db = PySqliteDatabase()
        init_db_model(db, mnemonic=None)
        clock = read_clock(db)
        update_clock(db, CrdtClock(clock.timestamp, clock.merkle_tree))
        out = capsys.readouterr().out
        assert "[clock:read]" in out and "[clock:update]" in out
        targets = [e.target for e in logger.recent_events()]
        assert "clock:read" in targets and "clock:update" in targets
    finally:
        logger.configure(False)
        logger.clear()


def test_ring_is_bounded():
    lg = Logger(enabled=True, capacity=4)
    for i in range(10):
        lg.log("dev", str(i))
    msgs = [e.message for e in lg.recent_events()]
    assert msgs == ["6", "7", "8", "9"]


def test_span_trace_annotations_fire_under_the_target_name():
    """With trace annotations enabled (VERDICT #7), every span opens a
    jax.profiler.TraceAnnotation named by the SAME target the
    log/metrics surfaces use; disabled spans touch nothing."""
    import evolu_tpu.utils.log as log_mod

    entered = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    lg = Logger()
    orig = log_mod._trace_annotation_cls
    try:
        log_mod._trace_annotation_cls = FakeAnnotation
        with lg.span("kernel:merkle", "reconcile_stream_finish", n=3):
            pass
        with lg.span("kernel:merge"):
            pass
    finally:
        log_mod._trace_annotation_cls = orig
    assert entered == [
        ("enter", "kernel:merkle|reconcile_stream_finish"),
        ("exit", "kernel:merkle|reconcile_stream_finish"),
        ("enter", "kernel:merge"),
        ("exit", "kernel:merge"),
    ]
    # Disabled (the default): spans never construct an annotation.
    entered.clear()
    with lg.span("kernel:merge"):
        pass
    assert entered == []


def test_enable_trace_annotations_real_jax_class():
    """The real jax.profiler.TraceAnnotation binds and runs (smoke —
    actual trace capture is benchmarks/kernel_trace.py)."""
    from evolu_tpu.utils.log import enable_trace_annotations
    import evolu_tpu.utils.log as log_mod

    try:
        enable_trace_annotations(True)
        assert log_mod._trace_annotation_cls is not None
        with Logger().span("kernel:merge", "smoke"):
            pass
    finally:
        enable_trace_annotations(False)
    assert log_mod._trace_annotation_cls is None
