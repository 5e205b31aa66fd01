"""Instrumentation overhead on the 1M-row reconcile path (slope method).

Acceptance gate for the observability PR: metrics must cost <=1% of the
1M-row reconcile. Two measurements, both per CLAUDE.md's slope rule
(never divide one wall time by its count — fixed overhead buries the
result):

1. The DEVICE leg is untouched by construction (obs never imports jax,
   tests/test_bench_liveness.py pins checksum + jit-cache equality), so
   the only possible cost is the HOST-side instrumentation sequence per
   batch. Measure exactly that sequence — the per-batch counter incs,
   histogram observes, span bookkeeping and flight append that
   `reconcile_owner_batches` + `plan_batch`-level code execute — via
   the slope between two repetition counts.

2. Anchor it against the measured per-batch reconcile wall time on this
   platform (the same two-point slope over fused iterations bench.py
   uses), and report the ratio.

Prints one JSON line.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import bench
from evolu_tpu.obs import anatomy, flight, ledger, metrics
from evolu_tpu.utils.log import logger

REPS_LO, REPS_HI = 200, 2000
ITERS_LO, ITERS_HI = 2, 10

# Conservation-ledger + sentinel gate (ISSUE 15): their combined
# per-batch cost must stay <= 0.1% of the config-2 reconcile leg.
LEDGER_GATE_FRACTION = 0.001


def instrumentation_sequence():
    """The host-side metric work ONE 1M-row reconcile batch performs:
    reconcile batch/owner observes + 8 shard-size observes + kernel
    routing counter (reconcile.py), one span close (histogram observe +
    flight append + duration aggregate, utils/log.py + obs), and the
    apply-route counter (apply.py). Deliberately a superset of the
    steady-state count."""
    metrics.observe("evolu_reconcile_batch_rows", 1_000_000,
                    buckets=metrics.COUNT_BUCKETS)
    metrics.observe("evolu_reconcile_batch_owners", 1_000,
                    buckets=metrics.COUNT_BUCKETS)
    for _ in range(8):
        metrics.observe("evolu_reconcile_shard_rows", 125_000,
                        buckets=metrics.COUNT_BUCKETS)
    metrics.inc("evolu_reconcile_kernel_total", variant="packed")
    metrics.inc("evolu_apply_batches_total", route="object")
    metrics.observe("evolu_kernel_span_ms", 12.5, target="kernel:reconcile")
    flight.record("kernel:reconcile", "batch", n=1_000_000)
    metrics.inc("evolu_winner_cache_hits_total", 250_000)
    metrics.inc("evolu_winner_cache_misses_total", 0)
    metrics.set_gauge("evolu_winner_cache_streaming", 0)


_OWNERS = [f"owner{i:04d}" for i in range(32)]


def ledger_sentinel_sequence():
    """The ledger + sentinel work ONE config-2 engine pass performs
    (32 requests / 32 owners / 1M rows): per-request relay ingress
    counts, the pass's pending-entry terminal classification, the
    recompile-sentinel gauge refresh, and the device→host pull wave
    instrumentation. Deliberately a superset (real passes skip
    zero-count stations for free)."""
    for o in _OWNERS:
        ledger.count(ledger.INGRESS_SYNC, 31250, owner=o)
    entry = ledger.pending()
    for o in _OWNERS:
        entry.count(ledger.STORE_INSERTED, 31250, owner=o)
        entry.count(ledger.STORE_DUPLICATE, 0, owner=o)
    entry.commit()
    # Recompile sentinel: two cache gauges + the flat-diff bookkeeping.
    metrics.set_gauge("evolu_jit_cache_size", 7, cache="merkle")
    metrics.set_gauge("evolu_jit_cache_size", 0, cache="mesh")
    # Pull-bandwidth plane: one output wave of the merkle kernel.
    metrics.inc("evolu_pull_bytes_total", 48_000_000)
    metrics.inc("evolu_pull_seconds_total", 3.0)
    metrics.observe("evolu_pull_wave_bytes", 48_000_000,
                    buckets=metrics.SIZE_BUCKETS)


def anatomy_sequence():
    """The stage-anatomy accounting ONE config-2 engine pass performs
    (ISSUE 16): the three runtime seam records (device dispatch / pull
    wave / host apply, each pricing a floor + feeding the decayed fit +
    share gauges) plus the two kernel-span folds the pass's spans
    trigger. Deliberately a superset of the steady state."""
    anatomy.record_stage("device_dispatch", 0.115, rows=1_000_000)
    anatomy.record_stage("pull_wave", 2.8, nbytes=48_000_000)
    anatomy.record_stage("host_apply", 1.4, rows=1_000_000)
    anatomy.record_span("kernel:reconcile", 115.0, rows=1_000_000)
    anatomy.record_span("kernel:merkle", 9.5, rows=1_000_000)


def _slope_ms(fn):
    """Slope between two repetition counts of a per-batch sequence."""
    def timed(reps):
        runs = []
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(reps):
                fn()
            runs.append(time.perf_counter() - t0)
        return statistics.median(runs)

    t_lo, t_hi = timed(REPS_LO), timed(REPS_HI)
    return (t_hi - t_lo) / (REPS_HI - REPS_LO) * 1e3  # ms per batch


def measure_instrumentation_ms():
    return _slope_ms(instrumentation_sequence)


def measure_ledger_sentinel_ms():
    return _slope_ms(ledger_sentinel_sequence)


def measure_anatomy_ms():
    return _slope_ms(anatomy_sequence)


def measure_reconcile_batch_ms():
    """Per-iteration wall time of the 1M-row reconcile pipeline on this
    platform, two-point slope over fused iterations (bench.py method,
    smaller iteration counts — this anchors a ratio, it is not the
    scored bench)."""
    from evolu_tpu.parallel.mesh import create_mesh, sharding

    mesh = create_mesh()
    n_dev = mesh.devices.size
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")
    with jax.enable_x64(True):
        cols, _ = bench.shard_layout(bench.build_columns(stored_winners=True), n_dev)
        args = [jax.device_put(cols[k], shd) for k in names]
        medians = {}
        for iters in (ITERS_LO, ITERS_HI):
            loop = bench.make_loop(mesh, iters)
            np.asarray(loop(*args))  # compile + warm
            runs = []
            for _ in range(5):
                t0 = time.perf_counter()
                np.asarray(loop(*args))
                runs.append(time.perf_counter() - t0)
            medians[iters] = statistics.median(runs)
    return (medians[ITERS_HI] - medians[ITERS_LO]) / (ITERS_HI - ITERS_LO) * 1e3


def main():
    logger.clear()
    instr_ms = measure_instrumentation_ms()
    ledger_ms = measure_ledger_sentinel_ms()
    anatomy.set_device_kind(anatomy.V5E)  # priced floors = the expensive path
    anatomy_ms = measure_anatomy_ms()
    batch_ms = measure_reconcile_batch_ms()
    print(json.dumps({
        "metric": "obs_instrumentation_overhead_on_1m_reconcile",
        "instrumentation_ms_per_batch": round(instr_ms, 5),
        "ledger_sentinel_ms_per_batch": round(ledger_ms, 5),
        "reconcile_ms_per_batch": round(batch_ms, 3),
        "overhead_fraction": round(instr_ms / batch_ms, 6),
        "overhead_pct": round(100 * instr_ms / batch_ms, 4),
        "pass_1pct_gate": instr_ms / batch_ms <= 0.01,
        "ledger_overhead_fraction": round(ledger_ms / batch_ms, 6),
        "ledger_overhead_pct": round(100 * ledger_ms / batch_ms, 4),
        "pass_ledger_0p1pct_gate": ledger_ms / batch_ms <= LEDGER_GATE_FRACTION,
        "anatomy_ms_per_batch": round(anatomy_ms, 5),
        "anatomy_overhead_fraction": round(anatomy_ms / batch_ms, 6),
        "anatomy_overhead_pct": round(100 * anatomy_ms / batch_ms, 4),
        "pass_anatomy_0p1pct_gate": anatomy_ms / batch_ms <= LEDGER_GATE_FRACTION,
        "device_graph_untouched": "pinned by tests/test_bench_liveness.py",
        "platform": jax.devices()[0].platform,
        "method": "two-point slope on both legs (fixed overhead cancelled)",
    }))


if __name__ == "__main__":
    jax.config.update("jax_enable_x64", True)
    main()
