"""Compile rehearsal for the chip, without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is DESCRIBED, not attached (`jax.experimental.topologies`): what it
refuses here — a misaligned Pallas slice, too much fast memory, a program
that does not fit — costs no chip time. Nothing runs, so these cases say
nothing about results or speed; they pin that the main path's device
programs still compile for a v5e, and that the Pallas scan kernels are
really in them (`tpu_custom_call`). A compile that passes is not a chip
run; chip_smoke.py is.

Code that asks `jax.default_backend()` sees the CPU here and would take
its CPU branch, so the cases steer `merge._use_pallas_scan` to its TPU
branch with monkeypatch — in the test, not through an option of the
program.

Tier-1 holds the scan kernels, the full-width Merkle kernel (seconds
each) and one compile of the relay's compact-delta kernel (about a
minute). The planner programs and the other compact shapes take one to
several minutes each — wide u64 sorts under x64 emulation, independent
of N — so they sit under the `slow` marker at the exact shapes
chip_smoke.py touches; a builder runs them before a chip call
(`pytest tests/test_tpu_compile.py -m slow -s`) and reads the printed
seconds as compile rehearsal, never as a chip result.

The topology is described inside a module-scoped fixture — never at
import, in a `skipif`, or in `parametrize`: only one process may hold
libtpu, and every pytest-xdist worker imports every test module.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from evolu_tpu.ops import bucket_size, merge, pallas_scan, winner_cache
from evolu_tpu.parallel import reconcile
from evolu_tpu.parallel.mesh import OWNERS_AXIS
from evolu_tpu.server import engine

N = 1 << 20  # the config-3 / bench batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one (the next run would warn and
    # compile again): cache off around this module.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_scan_route(monkeypatch):
    """The branch `_use_pallas_scan` takes on a TPU backend."""
    monkeypatch.setattr(
        merge, "_use_pallas_scan", lambda n: n >= merge._PALLAS_SCAN_MIN)


def _mesh(topo, n_devices):
    return Mesh(np.array(topo.devices[:n_devices]), (OWNERS_AXIS,))


def _sharded(mesh, *shape_dtypes):
    """ShapeDtypeStructs sharded on the owners axis ((n,) arrays) or,
    for (n_devices, cap) slot arrays, on their leading axis."""
    return [
        jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=NamedSharding(
                mesh, P(OWNERS_AXIS, *([None] * (len(shape) - 1)))))
        for shape, dtype in shape_dtypes
    ]


def _compile(fn, args, *, x64=True):
    with jax.enable_x64(x64):
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        return compiled.as_text(), time.perf_counter() - t0


# -- tier-1: the programs that compile in seconds ---------------------


@pytest.mark.parametrize("kernel,planes", [
    ("_scan_blocks", 5), ("_xor_scan_blocks", 2), ("_sum_scan_blocks", 3),
])
def test_pallas_scan_kernels_compile_for_v5e(topo, kernel, planes):
    """The three single-pass scan kernels at (8192, 128) u32 — 1M
    elements — traced outside the x64 scope as their wrappers do (an
    i64 grid index map fails TPU compilation)."""
    plane = jax.ShapeDtypeStruct(
        (N // 128, 128), jnp.uint32, sharding=SingleDeviceSharding(topo.devices[0]))
    text, _s = _compile(getattr(pallas_scan, kernel), [plane] * planes, x64=False)
    assert "tpu_custom_call" in text


def _merkle_case(variant, mesh):
    """(jitted kernel, argument shapes) of one engine Merkle kernel at
    N rows over `mesh`, shaped exactly as `engine.deltas_dispatch` /
    `deltas_finish` build them."""
    shard_size = N // mesh.devices.size
    cap = bucket_size(max(shard_size // 8, 64))
    if variant == "full":
        return engine._compiled_merkle_kernel(mesh), _sharded(
            mesh, ((N,), jnp.int64), ((N,), jnp.int32), ((N,), jnp.uint64),
            ((N,), jnp.bool_), ((N,), jnp.int64))
    delta = variant == "compact_delta"
    return engine._compiled_packed_kernel(mesh, cap, delta), _packed_upload(
        mesh, shard_size, delta)


def _packed_upload(mesh, shard_size, delta):
    """The ONE u64 buffer a pass uploads: 2S + 1 words a device for the
    delta variant, 2.5 S for the full-key one."""
    words = 2 * shard_size + 1 if delta else 2 * shard_size + shard_size // 2
    return _sharded(mesh, ((mesh.devices.size * words,), jnp.uint64))


def _check_merkle_kernel(topo, variant, n_devices):
    fn, args = _merkle_case(variant, _mesh(topo, n_devices))
    text, seconds = _compile(fn, args)
    assert "tpu_custom_call" in text  # each shard holds >= 2^15 rows
    if n_devices > 1:
        # The digest XOR all-reduce over ICI (XLA folds the
        # all_gather + local XOR of `xor_allreduce` into one).
        assert "all-reduce" in text
    return seconds


@pytest.mark.parametrize("variant,n_devices", [
    ("full", 1), ("full", 4), ("compact_delta", 1),
])
def test_engine_merkle_kernels_compile_for_v5e(topo, tpu_scan_route, variant,
                                               n_devices):
    """The relay's device pass at 2^20 rows with the Pallas XOR scan
    inside: the full-width kernel on one chip and sharded over four
    (seconds each), and the compact-delta kernel — the program every
    relay batch actually runs — on one chip. That one takes about a
    minute whatever N is (its global grouping sort and its stable
    compaction sort, not its size); its other shapes are slow cases."""
    _check_merkle_kernel(topo, variant, n_devices)


@pytest.mark.slow
@pytest.mark.parametrize("variant,n_devices", [
    ("compact", 1), ("compact", 4), ("compact_delta", 4),
])
def test_engine_compact_kernels_compile_for_v5e(topo, tpu_scan_route, variant,
                                                n_devices):
    seconds = _check_merkle_kernel(topo, variant, n_devices)
    print(f"\nCOMPILE_REHEARSAL merkle_{variant}@2^20x{n_devices}: {seconds:.1f} s "
          f"(sandbox compile for a described v5e — not a chip result)")


# -- slow: the planner programs, at the shapes chip_smoke.py touches --

_BATCH = 1 << 15  # the client phase's one batch bucket (25k rows)
_CAP = 1 << 15    # DeviceWinnerCache's starting capacity: <= 5k cells never grow it


def _u64(n):
    return ((n,), jnp.uint64)


def _i32(n):
    return ((n,), jnp.int32)


def _slow_cases(topo):
    """{case: (jitted program, argument shapes, holds a Pallas scan)}."""
    one, four = _mesh(topo, 1), _mesh(topo, 4)
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(*shape_dtypes):
        return [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shape_dtypes]

    shard_args = (_i32(N), _u64(N), _u64(N), _u64(N), _u64(N), ((N,), jnp.int64))
    stream = N // 4  # the relay phase loads 1M rows as four batches
    stream_cap = bucket_size(max(stream // 8, 64))
    mesh_cap, mesh_batch = 1 << 12, 1 << 13  # MeshShardedWinnerCache: 25k rows / 4
    slot2 = ((4, mesh_cap), jnp.uint64)
    return {
        # client phase, one chip
        "plan_full@2^15": (merge._plan_full_kernel, on_chip(
            _i32(_BATCH), _u64(_BATCH), _u64(_BATCH), _u64(_BATCH), _u64(_BATCH)),
            True),
        "cached_plan@cap2^15,2^15": (winner_cache._cached_plan_kernel, on_chip(
            _u64(_CAP), _u64(_CAP), _i32(_BATCH), _i32(_BATCH),
            _u64(_BATCH), _u64(_BATCH)), True),
        "seed@cap2^15,2^13": (winner_cache._seed_kernel, on_chip(
            _u64(_CAP), _u64(_CAP), _i32(1 << 13), _u64(1 << 13), _u64(1 << 13)),
            False),
        # kernel phase (and bench.py's kernel), one and four chips
        "shard_kernel@2^20x1": (reconcile._compiled_kernel(
            one, reconcile._shard_kernel), _sharded(one, *shard_args), True),
        "shard_kernel@2^20x4": (reconcile._compiled_kernel(
            four, reconcile._shard_kernel), _sharded(four, *shard_args), True),
        # relay phase, one chip: the streamed 250k-row batches
        "merkle_compact_delta@2^18x1": (
            engine._compiled_packed_kernel(one, stream_cap, True),
            _packed_upload(one, stream, True), True),
        # the served pass's smallest bucket, and a backfill's on four chips
        "merkle_compact_delta@64x1": (
            engine._compiled_packed_kernel(one, 64, True),
            _packed_upload(one, 64, True), False),
        "merkle_compact_delta@4x65536": (
            engine._compiled_packed_kernel(four, 1 << 13, True),
            _packed_upload(four, 1 << 16, True), True),
        # --chips 4: the mesh-sharded winner cache
        "sharded_plan@4x(cap2^12,2^13)": (
            winner_cache._sharded_plan_kernel(four),
            _sharded(four, slot2, slot2, _i32(4 * mesh_batch), _i32(4 * mesh_batch),
                     _u64(4 * mesh_batch), _u64(4 * mesh_batch)),
            False),  # 8192 rows per chip: under one Pallas tile, XLA scans
        "sharded_seed@4x(cap2^12,2^11)": (
            winner_cache._sharded_seed_kernel(four),
            _sharded(four, slot2, slot2, ((4, 1 << 11), jnp.int32),
                     ((4, 1 << 11), jnp.uint64), ((4, 1 << 11), jnp.uint64)),
            False),
    }


@pytest.mark.slow
@pytest.mark.parametrize("case", [
    "plan_full@2^15", "cached_plan@cap2^15,2^15", "seed@cap2^15,2^13",
    "shard_kernel@2^20x1", "shard_kernel@2^20x4", "merkle_compact_delta@2^18x1",
    "merkle_compact_delta@64x1", "merkle_compact_delta@4x65536",
    "sharded_plan@4x(cap2^12,2^13)", "sharded_seed@4x(cap2^12,2^11)",
])
def test_planner_programs_compile_for_v5e(topo, tpu_scan_route, case):
    fn, args, pallas = _slow_cases(topo)[case]
    text, seconds = _compile(fn, args)
    print(f"\nCOMPILE_REHEARSAL {case}: {seconds:.1f} s "
          f"(sandbox compile for a described v5e — not a chip result)")
    assert ("tpu_custom_call" in text) == pallas
