"""One device call a relay pass: one buffer up, one jit call, one
buffer back (PR 41).

The packed programs (`engine._compiled_packed_kernel`) are thin
wrappers in front of the compact Merkle kernel bodies: these cases hold
them to those bodies output for output, for both upload variants, at
the 64-row and the 4,096-row bucket, on one device and on the
eight-device virtual mesh (an owner split across devices included), and
hold the overflow re-run, the transfer counters and the one-program-a-
bucket rule.
"""

import functools

import jax
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from evolu_tpu.core.merkle import minute_deltas_host
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.ops import to_host_many, with_x64
from evolu_tpu.ops.host_parse import parse_timestamp_strings
from evolu_tpu.parallel.mesh import OWNERS_AXIS, create_mesh
from evolu_tpu.server import engine

BASE = 1_700_000_000_000


def _columns(owner_rows, spread_ms, minute_step=0):
    """→ the arguments of `deltas_dispatch` after the mesh, and the
    strings by owner. Row i of an owner is at BASE + i * spread_ms (+ i
    minutes where `minute_step`), so a spread of 2^33 ms leaves the
    delta variant's admission and `minute_step` makes every row its own
    (owner, minute) segment."""
    owners, flat = {}, []
    for o, n in enumerate(owner_rows):
        owners[f"u{o}"] = [
            timestamp_to_string(Timestamp(
                BASE + o * 977 + (i % 7) * spread_ms + i * minute_step * 60_000,
                i, f"{o + 1:016x}"))
            for i in range(n)
        ]
        flat.extend(owners[f"u{o}"])
    all_m, all_c, all_n, case_ok = parse_timestamp_strings(flat, with_case=True)
    owner_index, pos = {}, 0
    for o, msgs in owners.items():
        owner_index[o] = np.arange(pos, pos + len(msgs))
        pos += len(msgs)
    return (owner_index, all_m, all_c, all_n, case_ok, flat), owners


def _host_fold(owners):
    deltas, digest = {}, 0
    for o, msgs in owners.items():
        deltas[o], d = minute_deltas_host(msgs)
        digest ^= d
    return deltas, digest


def _owner_rows(bucket, n_devices):
    """Row counts that fill `n_devices` devices to `bucket` slots each,
    with one owner larger than an even device's share where there are
    several devices (it is split row-wise across them)."""
    per_device = bucket * 3 // 4
    small = [max(per_device // 9, 3)] * (8 * n_devices)
    return ([per_device * 2] if n_devices > 1 else []) + small


@with_x64
def _unpacked_reference(mesh, buf, k1, oix, cap, delta):
    """The four outputs of the kernel body the packed program wraps,
    from the columns the host reads back out of the one buffer."""
    n_dev = mesh.devices.size
    rows = buf.reshape(n_dev, -1)
    s = len(oix) // n_dev
    node = np.ascontiguousarray(rows[:, s:2 * s]).reshape(-1)
    spec = P(OWNERS_AXIS)
    if delta:
        halves = rows[:, :s].view(np.uint32)
        dmillis = np.ascontiguousarray(halves[:, 0::2]).reshape(-1)
        ownctr = np.ascontiguousarray(halves[:, 1::2]).reshape(-1)
        base = rows[:1, -1].astype(np.int64)
        assert (rows[:, -1] == rows[0, -1]).all()  # every device's tail
        body, args, in_specs = (engine._merkle_shard_kernel_compact_delta,
                                (dmillis, ownctr, node, base), (spec, spec, spec, P()))
    else:
        body, args, in_specs = (engine._merkle_shard_kernel_compact,
                                (k1, node, oix), (spec,) * 3)
    fn = jax.jit(shard_map(
        functools.partial(body, cap=cap), mesh=mesh, in_specs=in_specs,
        out_specs=(spec, spec, spec, P()), check_vma=False))
    packed, xors, counts, digest = (np.asarray(a) for a in fn(*args))
    return packed.reshape(n_dev, cap), xors.reshape(n_dev, cap), counts, int(digest)


@pytest.mark.parametrize("n_devices", [1, 8])
@pytest.mark.parametrize("bucket", [64, 4096])
@pytest.mark.parametrize("variant", ["delta", "full"])
def test_packed_program_matches_the_kernel_body_it_wraps(variant, bucket, n_devices):
    mesh = create_mesh(n_devices)
    spread = 977 if variant == "delta" else 1 << 33
    cols, owners = _columns(_owner_rows(bucket, n_devices), spread)
    with jax.enable_x64(True):
        deltas, digest, good, layout = engine._deltas_layout(mesh, *cols, None)
        buf, k1, oix, cap, delta, rows = layout
        assert delta == (variant == "delta")
        assert len(oix) == n_devices * bucket and rows == len(cols[5])
        words = 2 * bucket + 1 if delta else 2 * bucket + bucket // 2
        assert buf.shape == (n_devices * words,) and buf.dtype == np.uint64
        if n_devices > 1:  # the large owner's rows sit on several devices
            on = {int(d) for d in np.nonzero(
                (oix.reshape(n_devices, bucket) == good.index("u0")).any(axis=1))[0]}
            assert len(on) > 1
        (out,) = to_host_many(engine._compiled_packed_kernel(mesh, cap, delta)(buf))
    got = engine._unpack_outputs(out, n_devices, cap)
    want = _unpacked_reference(mesh, buf, k1, oix, cap, delta)
    for name, g, w in zip(("packed", "xors", "counts", "digest"), got, want):
        assert np.array_equal(g, w), name
    assert not (got[2] > cap).any()
    # and the whole call, against the host's fold of the strings
    assert engine.deltas_from_columns(mesh, *cols) == _host_fold(owners)


@pytest.mark.parametrize("n_devices", [1, 8])
def test_overflowing_batch_takes_the_full_width_rerun(n_devices, monkeypatch):
    """More (owner, minute) segments on a device than `cap`: the packed
    outputs say so in `counts`, and the re-run reads `node` back out of
    the one buffer."""
    mesh = create_mesh(n_devices)
    cols, owners = _columns([300] * (2 * n_devices) + [5], 977, minute_step=1)
    reruns = []
    full_width = engine._compiled_merkle_kernel

    def spy(m):
        reruns.append(m)
        return full_width(m)

    monkeypatch.setattr(engine, "_compiled_merkle_kernel", spy)
    state = engine.deltas_dispatch(mesh, *cols)
    cap = state[4][4]
    pulled = engine.deltas_pull(state)
    assert (engine._unpack_outputs(pulled[0], n_devices, cap)[2] > cap).any()
    assert engine.deltas_decode(state, pulled) == _host_fold(owners)
    assert reruns == [mesh]


def _moved(before):
    now = {
        "dispatches": metrics.get_counter("evolu_engine_device_dispatches_total"),
        "up": metrics.get_counter("evolu_engine_device_transfers_total", dir="up"),
        "down": metrics.get_counter("evolu_engine_device_transfers_total", dir="down"),
    }
    return now if before is None else {k: now[k] - before[k] for k in now}


@pytest.mark.parametrize("n_devices", [1, 8])
@pytest.mark.parametrize("variant", ["delta", "full"])
def test_one_dispatch_is_one_transfer_each_way(variant, n_devices):
    mesh = create_mesh(n_devices)
    cols, owners = _columns([9, 4, 30], 977 if variant == "delta" else 1 << 33)
    before = _moved(None)
    uploaded = metrics.get_counter(
        "evolu_engine_compact_upload_bytes_total", variant=variant)
    assert engine.deltas_from_columns(mesh, *cols) == _host_fold(owners)
    assert _moved(before) == {"dispatches": 1, "up": 1, "down": 1}
    words = 2 * 64 + 1 if variant == "delta" else 2 * 64 + 32
    assert metrics.get_counter(
        "evolu_engine_compact_upload_bytes_total", variant=variant
    ) - uploaded == n_devices * words * 8


def test_one_program_a_bucket_not_a_batch():
    """`merkle_jit_cache_size()` grows by one program a (mesh, cap,
    bucket): batches of other row counts inside a bucket add none."""
    mesh = create_mesh(2)  # a mesh no other case of this file compiles for
    sizes = []
    for owner_rows in ([3, 5], [40, 17, 2], [60], [200, 40], [90, 90, 11], [64, 64]):
        cols, owners = _columns(owner_rows, 977)
        assert engine.deltas_from_columns(mesh, *cols) == _host_fold(owners)
        sizes.append(engine.merkle_jit_cache_size())
    # buckets a device: 64, 64, 64, then 128, 128, 64
    assert sizes[0] == sizes[1] == sizes[2]
    assert sizes[3] == sizes[0] + 1
    assert sizes[4] == sizes[5] == sizes[3]
