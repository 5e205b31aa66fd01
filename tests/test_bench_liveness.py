"""bench.py graph-liveness fence (VERDICT r3 weak #3).

The r2/early-r3 measurement bug: the fori_loop checksum consumed only
the masks + digest, so XLA dead-code-eliminated the whole Merkle
minute-segment stage from the timed graph and the bench silently timed
a smaller pipeline (under-reported 2.3×). bench.py now folds EVERY
kernel output into the carry; this test pins that property so the bug
class can never return: for each of the 9 `_shard_kernel` outputs,
perturbing just that output must change the checksum. If a future edit
drops an output from the fold, its perturbation becomes invisible and
the test fails — i.e. "stub any pipeline stage and nothing fails" is
now false by construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from evolu_tpu.parallel.mesh import create_mesh, sharding
from evolu_tpu.parallel.reconcile import _shard_kernel, scatter_shard_kernel

N_OUTPUTS = 9  # xor_s, upsert_s, i_s, owner/minute/seg_end/seg_xor/valid, digest

# The scatter plan kernel (ISSUE 4) shares the 9-output contract; its
# table covers the fence's perturbed cell range (cells < 128, one
# fence iteration XORs bit 18 at most — i=0 only, so no relabel).
_KERNELS = {
    "sort": _shard_kernel,
    "scatter": scatter_shard_kernel(1 << 19),
}


def _perturbing_kernel(base_kernel, j):
    """The real kernel with output j nudged by one unit/flip — the
    minimal observable change a live fold must propagate."""

    def kernel(*args):
        outs = list(base_kernel(*args))
        # Fail loudly on arity drift: a 10th output would silently
        # escape the fence otherwise.
        assert len(outs) == N_OUTPUTS, f"kernel grew to {len(outs)} outputs"
        o = outs[j]
        if o.ndim == 0:
            outs[j] = o + jnp.ones((), o.dtype) if o.dtype != jnp.bool_ else ~o
        elif o.dtype == jnp.bool_:
            outs[j] = o.at[0].set(~o[0])
        else:
            outs[j] = o.at[0].add(jnp.ones((), o.dtype))
        return tuple(outs)

    return kernel


@pytest.fixture(scope="module")
def tiny_setup():
    mesh = create_mesh()
    n_dev = mesh.devices.size
    cols, _ = bench.shard_layout(
        bench.build_columns(n=512, owners=16, stored_winners=True), n_dev
    )
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")
    with jax.enable_x64(True):
        args = [jax.device_put(cols[k], shd) for k in names]
    return mesh, args


@pytest.mark.parametrize("variant", list(_KERNELS))
def test_every_kernel_output_is_live_in_the_checksum(tiny_setup, variant):
    mesh, args = tiny_setup
    base_kernel = _KERNELS[variant]
    # iters=1: with more fused iterations a bool-flip perturbation's
    # ±1 checksum delta could cancel across iterations (flipped element
    # True in one, False in the next) and falsely report a live output
    # as dead; a single iteration makes every perturbation's delta
    # nonzero by construction.
    with jax.enable_x64(True):
        base = int(bench.make_loop(mesh, 1, kernel=base_kernel)(*args))
        dead = []
        for j in range(N_OUTPUTS):
            loop = bench.make_loop(mesh, 1, kernel=_perturbing_kernel(base_kernel, j))
            if int(loop(*args)) == base:
                dead.append(j)
    assert dead == [], (
        f"[{variant}] outputs {dead} do not feed the bench checksum — XLA is "
        f"free to DCE their producing stages out of the timed graph"
    )


def test_metrics_do_not_touch_the_bench_graph(tiny_setup):
    """Instrumentation is host-side by contract: flipping the metrics
    registry on/off must leave the bench checksum bit-identical AND
    cause zero additional jit compilations (a recompile would mean an
    instrumentation value leaked into a traced graph as a constant, or
    an op was inserted into the fused pipeline)."""
    from evolu_tpu.obs import metrics

    mesh, args = tiny_setup
    loop = bench.make_loop(mesh, 1)
    with jax.enable_x64(True):
        metrics.set_enabled(False)
        try:
            base = int(loop(*args))
            cache_size = loop._cache_size()
            metrics.set_enabled(True)
            with_metrics = int(loop(*args))
            cache_size_after = loop._cache_size()
        finally:
            metrics.set_enabled(True)
    assert with_metrics == base, "metrics changed the bench checksum"
    assert cache_size_after == cache_size, (
        "enabling metrics added jit cache misses (recompiles) to the "
        "timed pipeline"
    )


def test_tracing_does_not_touch_the_bench_graph(tiny_setup):
    """ISSUE 10's twin of the metrics fence: with tracing enabled at
    100% sampling AND an active ambient span around the timed loop
    (the worst case — every log-span mirror fires), the bench checksum
    must stay bit-identical and the jit cache-miss count flat. Spans
    are host-side bookkeeping by contract; a recompile here would mean
    a trace value leaked into a traced graph."""
    from evolu_tpu.obs import trace

    mesh, args = tiny_setup
    loop = bench.make_loop(mesh, 1)
    with jax.enable_x64(True):
        trace.set_enabled(False)
        try:
            base = int(loop(*args))
            cache_size = loop._cache_size()
            trace.set_enabled(True)
            trace.set_sample_rate(1.0)
            root = trace.start_span("bench.guard")
            with root, trace.use(root.context):
                with_tracing = int(loop(*args))
            cache_size_after = loop._cache_size()
        finally:
            trace.set_enabled(True)
    assert with_tracing == base, "tracing changed the bench checksum"
    assert cache_size_after == cache_size, (
        "enabling tracing added jit cache misses (recompiles) to the "
        "timed pipeline"
    )


def test_ledger_does_not_touch_the_bench_graph(tiny_setup):
    """ISSUE 15's twin of the metrics/tracing fences: with the
    conservation ledger HOT (enabled, counts posting around and
    between loop invocations, a pending entry committing mid-flight),
    the bench checksum must stay bit-identical and both the loop's jit
    cache-miss count and the engine's `merkle_jit_cache_size()` flat.
    The ledger is host-side dict arithmetic by contract — a recompile
    here would mean a count leaked into a traced graph."""
    from evolu_tpu.obs import ledger
    from evolu_tpu.server import engine as eng_mod

    mesh, args = tiny_setup
    loop = bench.make_loop(mesh, 1)
    # The ledger is a process singleton: under `--dist loadfile` earlier
    # files on this worker leave their (legitimately unbalanced,
    # mid-stream) counts in it, and the audit below must see only this
    # test's own.
    ledger.reset()
    with jax.enable_x64(True):
        ledger.set_enabled(False)
        try:
            base = int(loop(*args))
            cache_size = loop._cache_size()
            engine_cache = eng_mod.merkle_jit_cache_size()
            ledger.set_enabled(True)
            ledger.count(ledger.INGRESS_SYNC, 512, owner="bench-owner")
            entry = ledger.pending()
            entry.count(ledger.STORE_INSERTED, 512, owner="bench-owner")
            with_ledger = int(loop(*args))
            entry.commit()
            assert ledger.audit(at_barrier=True) == []
            cache_size_after = loop._cache_size()
            engine_cache_after = eng_mod.merkle_jit_cache_size()
        finally:
            ledger.set_enabled(True)
            ledger.reset()
    assert with_ledger == base, "the ledger changed the bench checksum"
    assert cache_size_after == cache_size, (
        "enabling the ledger added jit cache misses (recompiles) to the "
        "timed pipeline"
    )
    assert engine_cache_after == engine_cache, (
        "the ledger moved the engine's merkle jit cache"
    )


def _stage_anatomy():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    import stage_anatomy as sa

    return sa


def _anatomy_device_stages():
    return list(_stage_anatomy().DEVICE_STAGES)


@pytest.mark.parametrize("stage", _anatomy_device_stages())
def test_every_truncated_variant_output_is_live(tiny_setup, stage):
    """ISSUE 16: the stage-anatomy harness times TRUNCATED pipeline
    variants, so the DCE fence must hold per variant, not just for the
    full kernel — for the variant ending at `stage`, perturbing each
    output that stage ADDED must move the variant's checksum. (Earlier
    stages' outputs are pinned by their own variant's case.)"""
    sa = _stage_anatomy()
    mesh, args = tiny_setup
    kernel = sa.build_variant(stage)
    arity = sa.variant_arity(stage)
    with jax.enable_x64(True):
        base = int(sa.make_variant_loop(mesh, 1, kernel)(*args))
        dead = []
        for j in sa.stage_output_indices(stage):
            loop = sa.make_variant_loop(
                mesh, 1, sa.perturbing_kernel(kernel, j, arity))
            if int(loop(*args)) == base:
                dead.append(j)
    assert dead == [], (
        f"[{stage}] outputs {dead} do not feed the variant checksum — the "
        f"anatomy harness would time a DCE'd (smaller) pipeline"
    )


def test_anatomy_does_not_touch_the_bench_graph(tiny_setup):
    """ISSUE 16's twin of the metrics/tracing/ledger fences: with the
    stage-anatomy accountant HOT (device kind set, stage records posting
    around and between loop invocations — the engine seams call it per
    batch), the bench checksum must stay bit-identical and the jit
    cache-miss count flat. Stage accounting is host-side float/dict
    arithmetic by contract."""
    from evolu_tpu.obs import anatomy, metrics

    mesh, args = tiny_setup
    loop = bench.make_loop(mesh, 1)
    prev_kind = anatomy.get_device_kind()
    with jax.enable_x64(True):
        metrics.set_enabled(False)
        try:
            base = int(loop(*args))
            cache_size = loop._cache_size()
            metrics.set_enabled(True)
            anatomy.set_device_kind(anatomy.V5E)
            anatomy.record_stage("device_dispatch", 0.105, rows=512)
            with_anatomy = int(loop(*args))
            anatomy.record_stage("host_apply", 0.002, rows=512)
            anatomy.record_stage("pull_wave", 0.001, nbytes=4096)
            cache_size_after = loop._cache_size()
        finally:
            metrics.set_enabled(True)
            anatomy.set_device_kind(prev_kind)
            anatomy.reset()
    assert with_anatomy == base, "stage accounting changed the bench checksum"
    assert cache_size_after == cache_size, (
        "stage accounting added jit cache misses (recompiles) to the "
        "timed pipeline"
    )


def test_checksum_depends_on_the_data():
    """Same loop, different input data → different checksum (guards a
    degenerate fold that collapses to a constant)."""
    mesh = create_mesh()
    n_dev = mesh.devices.size
    shd = sharding(mesh)
    names = ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")
    with jax.enable_x64(True):
        loop = bench.make_loop(mesh, 2)
        vals = []
        for seed in (7, 8):
            cols, _ = bench.shard_layout(
                bench.build_columns(n=512, owners=16, seed=seed, stored_winners=True),
                n_dev,
            )
            vals.append(int(loop(*[jax.device_put(cols[k], shd) for k in names])))
    assert vals[0] != vals[1]
