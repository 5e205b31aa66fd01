"""Mesh-sharded engine scaling: msgs/s vs device count (ISSUE 12).

One child process per device count (1 → 2 → 4 → 8 virtual CPU
devices; each child re-enters this file with `JAX_PLATFORMS=cpu` and
`--xla_force_host_platform_device_count=N`, and the parent never
imports jax — a chip belongs to one process, and this CPU bench takes
none): the child drives
`BatchReconciler.run_batch_wire` with a `MeshContext` (stable
owner→device placement — the sharded engine path) over deterministic
multi-owner push+pull rounds.

Method (CLAUDE.md timing discipline): per child, the SLOPE between a
low and a high round count on fresh stores after a jit warmup —
msgs/s = Δmsgs/Δwall, so compile/setup cancels. EVERY response byte
folds into a crc32 checksum that is printed (liveness: no serving leg
can be skipped unnoticed), and the child asserts the PARITY GATE —
responses + SQLite end state byte-identical to a SINGLE-DEVICE plain
engine — before any number is reported. The parent additionally pins
the final-store checksum identical across all device counts.

HONESTY: this container is 1-core. The virtual CPU mesh shares that
core, so the msgs/s-vs-devices slope here measures sharding OVERHEAD
(layout, padding, collective emulation), not ICI speedup — flat-to-
slightly-down is the expected CPU shape. The slope over real chips is
the claim this bench exists to measure: not measured.

Prints ONE JSON line. `--smoke` runs devices (1, 2) with a tiny
workload — the CI parity gate.
"""

import json
import os
import re
import subprocess
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEVICES = (1, 2, 4, 8)
SMOKE = "--smoke" in sys.argv

OWNERS = 16 if SMOKE else 48
BATCH_OWNERS = 8 if SMOKE else 16
MSGS = 6 if SMOKE else 20
ROUNDS_LO, ROUNDS_HI = (1, 2) if SMOKE else (2, 6)
BASE = 1_700_000_000_000


def _child_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["_MESH_BENCH_CHILD"] = str(n_devices)
    return env


def _rounds():
    """Deterministic traffic: every round, BATCH_OWNERS-owner batches
    pushing fresh windows (with one overlapping duplicate row per
    owner after round 0) and pulling against an empty client tree."""
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.sync import protocol

    def msgs(node, start, n):
        return tuple(
            protocol.EncryptedCrdtMessage(
                timestamp_to_string(Timestamp(BASE + (start + i) * 1000, 0, node)),
                b"ct%d" % (start + i),
            )
            for i in range(n)
        )

    out = []
    for rnd in range(ROUNDS_HI):
        batches = []
        for b0 in range(0, OWNERS, BATCH_OWNERS):
            reqs = []
            for i in range(b0, min(b0 + BATCH_OWNERS, OWNERS)):
                node = f"{i + 1:016x}"
                start = max(rnd * (MSGS - 1), 0)  # 1-row overlap per round
                reqs.append(protocol.SyncRequest(
                    msgs(node, start, MSGS), f"bench-u{i:03d}", node, "{}"
                ))
            batches.append(tuple(reqs))
        out.append(batches)
    return out


def _store_crc(store) -> int:
    crc = 0
    for s in store.shards:
        for row in s.db.exec(
            'SELECT "timestamp", "userId", "content" FROM "message" '
            'ORDER BY "timestamp", "userId"'
        ):
            crc = zlib.crc32(repr(row).encode(), crc)
        for row in s.db.exec(
            'SELECT "userId", "merkleTree" FROM "merkleTree" ORDER BY "userId"'
        ):
            crc = zlib.crc32(repr(row).encode(), crc)
    return crc


def _drive(engine_factory, rounds_n, traffic):
    """Serve `rounds_n` rounds on a FRESH store; → (wall_s, msgs, crc,
    store_crc)."""
    from evolu_tpu.server.relay import ShardedRelayStore

    store = ShardedRelayStore(shards=4)
    eng = engine_factory(store)
    crc = 0
    n_msgs = 0
    t0 = time.perf_counter()
    try:
        for rnd in range(rounds_n):
            for reqs in traffic[rnd]:
                for w in eng.run_batch_wire(reqs):
                    crc = zlib.crc32(w, crc)
                n_msgs += sum(len(r.messages) for r in reqs)
        wall = time.perf_counter() - t0
        return wall, n_msgs, crc, _store_crc(store)
    finally:
        eng.close()
        store.close()


def child(n_devices: int) -> None:
    import jax

    assert len(jax.devices()) == n_devices, (jax.devices(), n_devices)
    from evolu_tpu.parallel.mesh import MeshContext, create_mesh
    from evolu_tpu.server.engine import BatchReconciler

    ctx = MeshContext()
    assert ctx.n_shards == n_devices
    traffic = _rounds()

    def mesh_engine(store):
        return BatchReconciler(store, mesh_ctx=ctx)

    def single_engine(store):
        return BatchReconciler(store, mesh=create_mesh(1))

    # Parity gate FIRST (fresh stores, full traffic): sharded responses
    # and end state byte-identical to the single-device plain engine.
    from evolu_tpu.server.relay import ShardedRelayStore

    ms, ss = ShardedRelayStore(shards=4), ShardedRelayStore(shards=4)
    me, se = mesh_engine(ms), single_engine(ss)
    try:
        for rnd in range(ROUNDS_HI):
            for reqs in traffic[rnd]:
                assert me.run_batch_wire(reqs) == se.run_batch_wire(reqs), (
                    "PARITY GATE FAILED: sharded responses != single-device"
                )
        assert _store_crc(ms) == _store_crc(ss), (
            "PARITY GATE FAILED: sharded end state != single-device"
        )
    finally:
        me.close()
        se.close()
        ms.close()
        ss.close()

    # Slope: warmup (compiles every bucket), then lo and hi rounds.
    _drive(mesh_engine, 1, traffic)
    wall_lo, msgs_lo, _crc_lo, _ = _drive(mesh_engine, ROUNDS_LO, traffic)
    wall_hi, msgs_hi, crc_hi, store_crc = _drive(mesh_engine, ROUNDS_HI, traffic)
    slope = (msgs_hi - msgs_lo) / max(wall_hi - wall_lo, 1e-9)
    print(json.dumps({
        "devices": n_devices,
        "msgs_per_s_slope": round(slope, 1),
        "wall_lo_s": round(wall_lo, 4), "wall_hi_s": round(wall_hi, 4),
        "msgs_hi": msgs_hi,
        "response_crc": crc_hi,
        "store_crc": store_crc,
        "parity": "ok",
    }))


def main() -> None:
    devices = DEVICES[:2] if SMOKE else DEVICES
    results = []
    for n in devices:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)]
            + (["--smoke"] if SMOKE else []),
            env=_child_env(n), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, timeout=1800,
        )
        if proc.returncode != 0:
            sys.stdout.write(proc.stdout)
            raise SystemExit(f"mesh bench child ({n} devices) failed")
        results.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    # End state must be IDENTICAL across device counts (the cross-
    # device-count half of the parity claim).
    crcs = {r["store_crc"] for r in results}
    assert len(crcs) == 1, f"end state diverged across device counts: {results}"
    print(json.dumps({
        "bench": "mesh_engine",
        "smoke": SMOKE,
        "platform": "cpu-virtual-mesh",
        "rounds": [ROUNDS_LO, ROUNDS_HI],
        "owners": OWNERS,
        "per_request_msgs": MSGS,
        "store_crc": results[0]["store_crc"],
        "slope_msgs_per_s_by_devices": {
            str(r["devices"]): r["msgs_per_s_slope"] for r in results
        },
        "parity": "ok",
    }))


if __name__ == "__main__":
    if os.environ.get("_MESH_BENCH_CHILD"):
        child(int(os.environ["_MESH_BENCH_CHILD"]))
    else:
        main()
