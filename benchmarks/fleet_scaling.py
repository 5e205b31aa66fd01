"""Owner-sharded relay fleet: process-count ingest scaling + byte
identity + live rebalance (server/fleet.py).

The claim behind the fleet tier: the full-system wall is HOST-bound
(SQLite btree ~0.72M rows/s/core, one Python process ≈ one core), so
partitioning owners across N relay PROCESSES should scale aggregate
ingest with process count while keeping every owner's end state
byte-identical to a single relay — and a ring change should move
owners between relays with zero lost ACKed writes, cut over at the
per-owner Merkle watermark.

Measured here directly, with REAL processes (each leg spawns
`python -m evolu_tpu.server.fleet` workers — plain subprocesses, one
store each, scoped gossip between them) and M client threads pushing a
Zipf-skewed owner workload through the real routing path (random
first relay, learned 307 routes, 503-backoff retries):

* leg `single`: 1 relay ingests the whole workload → the oracle state
  (per-owner tree text + row crc) and the baseline msgs/s.
* leg `fleet`: N relays, same workload → aggregate msgs/s, then every
  owner's PRIMARY state — and each of its R replicas after gossip —
  must be byte-identical to the oracle.
* leg `rebalance`: relay N+1 joins via `POST /fleet/reload` WHILE a
  writer keeps pushing; moved owners snapshot-install on the gainer
  and cut over at the watermark (counter-asserted via /stats), and
  every ACKed write must exist in the final fleet state.

HONESTY (docs/BENCHMARKS.md): thread overlap inside one Python
process is serial — the scaling assertion (aggregate >= 2x single for
3 processes) is only asserted when `os.cpu_count()` actually offers a
core per relay; on a 1-core container the measured ratio is reported
as-is (expect ~1x — the point of the bench is that the LIMIT moves
from "one process" to "core count"). Correctness assertions
(byte-identity, zero lost ACKs, watermark cutover) always run.

Prints ONE JSON line. `--smoke` runs a tiny 2-relay CI pass.
"""

import argparse
import json
import os
import random
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import zlib

os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string  # noqa: E402
from evolu_tpu.server.fleet import HashRing  # noqa: E402
from evolu_tpu.sync import protocol  # noqa: E402
from evolu_tpu.sync.client import _http_post  # noqa: E402
from evolu_tpu.utils.config import FleetConfig  # noqa: E402

BASE = 1_700_000_000_000
NODE = "00000000000000bb"


# -- fleet-of-processes harness --


def _free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class FleetProcs:
    """N `python -m evolu_tpu.server.fleet` worker processes sharing
    one FleetConfig."""

    def __init__(self, n, version=1, seed=0, repl_interval=0.25):
        self.ports = _free_ports(n)
        self.urls = [f"http://127.0.0.1:{p}" for p in self.ports]
        self.seed = seed
        self.repl_interval = repl_interval
        self.config = FleetConfig(relays=tuple(self.urls), version=version,
                                  replication_factor=min(2, n), seed=seed)
        self.procs = []
        for port, url in zip(self.ports, self.urls):
            self.procs.append(self._spawn(port, url, self.config))
        self._await_ready(self.procs)

    def _spawn(self, port, url, config):
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            _REPO + (os.pathsep + env["PYTHONPATH"]
                     if env.get("PYTHONPATH") else "")
        )
        return subprocess.Popen(
            [sys.executable, "-m", "evolu_tpu.server.fleet",
             "--port", str(port), "--self-url", url,
             "--config-json", json.dumps(config.to_json()),
             "--replication-interval-s", str(self.repl_interval)],
            env=env, stdout=subprocess.PIPE, text=True,
        )

    def _await_ready(self, procs, timeout=60):
        import select

        waiting = {p.stdout.fileno(): p for p in procs}
        deadline = time.time() + timeout
        while waiting and time.time() < deadline:
            dead = [p for p in procs if p.poll() is not None]
            if dead:
                raise RuntimeError(
                    f"{len(dead)} fleet workers exited at startup "
                    f"(rc={[p.returncode for p in dead]})"
                )
            ready, _, _ = select.select(list(waiting), [], [], 0.2)
            for fd in ready:
                if "READY" in waiting[fd].stdout.readline():
                    del waiting[fd]
        if waiting:
            raise RuntimeError(f"{len(waiting)} fleet workers did not come up")

    def join(self, version):
        """Spawn one MORE relay and push the grown ring to EVERY
        member (the static config reload) → the new member's url.
        Order matters: the SURVIVORS reload first (their scoped
        summaries must know the new ring before the joiner asks), the
        joiner's reload comes last — a reconcile push that kicks its
        snapshot rebalance sweep."""
        (port,) = _free_ports(1)
        url = f"http://127.0.0.1:{port}"
        old_urls = list(self.urls)
        self.urls.append(url)
        self.ports.append(port)
        new_cfg = FleetConfig(
            relays=tuple(self.urls), version=version,
            replication_factor=min(2, len(self.urls)), seed=self.seed,
        )
        proc = self._spawn(port, url, new_cfg)
        self.procs.append(proc)
        self._await_ready([proc])
        body = json.dumps(new_cfg.to_json()).encode()
        for u in old_urls + [url]:
            req = urllib.request.Request(u + "/fleet/reload", data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
        self.config = new_cfg
        return url

    def all_serving(self):
        for u in self.urls:
            try:
                with urllib.request.urlopen(u + "/health", timeout=5) as r:
                    if r.status != 200:
                        return False
            except urllib.error.HTTPError:
                return False
            except OSError:
                return False
        return True

    def stats(self, url):
        with urllib.request.urlopen(url + "/stats", timeout=10) as r:
            return json.loads(r.read())

    def stop(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 - wedged: escalate AND reap
                p.kill()
                p.wait(timeout=10)
        self.procs = []


# -- workload --


def _zipf_counts(owners, total, s, rng):
    w = [1.0 / (i + 1) ** s for i in range(owners)]
    z = sum(w)
    counts = [max(1, int(total * wi / z)) for wi in w]
    while sum(counts) > total:
        counts[counts.index(max(counts))] -= 1
    i = 0
    while sum(counts) < total:
        counts[i % owners] += 1
        i += 1
    rng.shuffle(counts)
    return counts


def _build_workload(owners, total, batch, zipf_s, seed, t0=0):
    """→ (requests, per_owner_timestamps): requests are
    (owner_id, encoded SyncRequest body, n_messages), shuffled."""
    rng = random.Random(seed)
    counts = _zipf_counts(owners, total, zipf_s, rng)
    requests = []
    per_owner = {}
    for k in range(owners):
        uid = f"owner{k:04d}"
        ts = [
            timestamp_to_string(Timestamp(BASE + (t0 + j) * 500, 0,
                                          f"{k + 1:016x}"))
            for j in range(counts[k])
        ]
        per_owner[uid] = ts
        for i in range(0, len(ts), batch):
            chunk = ts[i : i + batch]
            msgs = tuple(
                protocol.EncryptedCrdtMessage(t, b"ct-%d-%s" % (k, t[:29].encode()))
                for t in chunk
            )
            requests.append((uid, protocol.encode_sync_request(
                protocol.SyncRequest(msgs, uid, NODE, "{}")), len(chunk)))
    rng.shuffle(requests)
    return requests, per_owner


def _ingest(requests, relay_urls, threads, deadline_s=600):
    """Push every request through the real routing path: random first
    relay, follow 307s (cache the learned route), ride _http_post's
    429/503/connection backoff, retry rounds until ACKed. → (wall_s,
    acked dict owner→msgs)."""
    routes = {}
    acked = {}
    lock = threading.Lock()
    idx = {"i": 0}
    errors = []

    def worker(tid):
        rng = random.Random(1000 + tid)
        while True:
            with lock:
                i = idx["i"]
                if i >= len(requests):
                    return
                idx["i"] = i + 1
            uid, body, n = requests[i]
            stop_at = time.time() + deadline_s
            while True:
                url = routes.get(uid) or rng.choice(relay_urls) + "/"
                try:
                    _http_post(url, body)
                    with lock:
                        acked[uid] = acked.get(uid, 0) + n
                    break
                except urllib.error.HTTPError as e:
                    loc = e.headers.get("Location") if e.headers else None
                    if e.code == 307 and loc:
                        routes[uid] = loc
                        continue
                    routes.pop(uid, None)
                    if time.time() > stop_at:
                        errors.append((uid, repr(e)))
                        return
                    time.sleep(0.05)
                except OSError as e:
                    routes.pop(uid, None)
                    if time.time() > stop_at:
                        errors.append((uid, repr(e)))
                        return
                    time.sleep(0.05)

    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} requests never ACKed: {errors[:3]}")
    return wall, acked


# -- state readback (the oracle comparison surface) --


def _owner_state(url, uid):
    """(tree text, rows crc32, row count) for one owner as served by
    `url`, read through the replication pull (empty peer_url = the
    unscoped oracle read), looping past the per-owner response cap."""
    crc = 0
    count = 0
    since = ""
    tree = ""
    while True:
        body = protocol.encode_replica_pull(
            protocol.ReplicaPull(((uid, since),), "bench-read"))
        resp = protocol.decode_replica_pull_response(
            _http_post(url + "/replicate/pull", body))
        if not resp.chunks:
            break
        om = resp.chunks[0]
        tree = om.merkle_tree
        if not om.messages:
            break
        for m in om.messages:
            crc = zlib.crc32(m.timestamp.encode(), crc)
            crc = zlib.crc32(m.content, crc)
            count += 1
        since = om.messages[-1].timestamp
    return tree, crc, count


def _await(predicate, deadline_s, what):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.2)
    raise RuntimeError(f"timed out waiting for {what}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2-relay CI pass (correctness only)")
    ap.add_argument("--relays", type=int, default=3)
    ap.add_argument("--owners", type=int, default=32)
    ap.add_argument("--messages", type=int, default=24_000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--zipf", type=float, default=1.1)
    args = ap.parse_args()

    if args.smoke:
        args.relays, args.owners, args.messages = 2, 12, 1_500
        args.batch, args.threads = 32, 4
    cpus = os.cpu_count() or 1
    assert_scaling = (not args.smoke) and cpus >= args.relays

    requests, per_owner = _build_workload(
        args.owners, args.messages, args.batch, args.zipf, seed=42)
    owners = sorted(per_owner)
    total = sum(len(v) for v in per_owner.values())

    # -- leg 1: single relay (the oracle) --
    single = FleetProcs(1)
    try:
        wall, acked = _ingest(requests, single.urls, args.threads)
        assert sum(acked.values()) == total
        oracle = {uid: _owner_state(single.urls[0], uid) for uid in owners}
        single_rate = total / wall
        leg_single = {"relays": 1, "wall_s": round(wall, 3),
                      "msgs_per_s": round(single_rate)}
    finally:
        single.stop()
    for uid in owners:
        assert oracle[uid][2] == len(per_owner[uid]), uid

    # -- leg 2: N-relay fleet, same workload --
    fleet = FleetProcs(args.relays)
    try:
        ring = HashRing(fleet.config)
        wall, acked = _ingest(requests, fleet.urls, args.threads)
        assert sum(acked.values()) == total
        fleet_rate = total / wall
        # Byte-identity at EVERY placed relay. Any of an owner's R
        # placed relays accepts its writes locally (multi-master
        # within the replica set — a client's random first relay may
        # be the replica, not the primary), so identity is asserted at
        # the scoped-gossip fixpoint, primary and replica alike.
        def replicas_converged():
            for uid in owners:
                for url in ring.placement(uid):
                    if _owner_state(url, uid) != oracle[uid]:
                        return False
            return True

        _await(replicas_converged, 120, "replica gossip convergence")
        # Scoped replication: a non-placed relay must NOT hold a copy.
        strays = 0
        for uid in owners:
            for url in fleet.urls:
                if url not in ring.placement(uid):
                    if _owner_state(url, uid)[2] != 0:
                        strays += 1
        assert strays == 0, f"{strays} owner copies outside placement"

        # -- leg 3: ring change under live writes. The live writer
        # covers only the FIRST HALF of the owner ids: live writes
        # landing on the joiner before its sweep legitimately divert
        # those owners to the gossip-drain path, so keeping half the
        # owners quiet guarantees (whenever any quiet owner moves)
        # that the snapshot-install path is exercised too. --
        extra_reqs, extra_owner = _build_workload(
            max(2, args.owners // 2), max(args.owners * 8, total // 10),
            args.batch, args.zipf, seed=43, t0=10**6)
        writer_out = {}

        def writer():
            try:
                writer_out["result"] = _ingest(extra_reqs, fleet.urls,
                                               max(2, args.threads // 2))
            except BaseException as e:  # noqa: BLE001 - re-raised after
                # join: a thread-swallowed failure here would otherwise
                # surface as an unrelated KeyError masking the real
                # "requests never ACKed" diagnosis.
                writer_out["error"] = e

        wt = threading.Thread(target=writer)
        wt.start()
        new_url = fleet.join(version=2)
        new_ring = HashRing(fleet.config)
        moved = [uid for uid in owners
                 if new_ring.primary(uid) != ring.primary(uid)]
        _await(fleet.all_serving, 120, "post-reload readiness")
        wt.join()
        if "error" in writer_out:
            raise writer_out["error"]
        _wall2, acked2 = writer_out["result"]
        assert sum(acked2.values()) == sum(len(v) for v in extra_owner.values())
        expected = {
            uid: sorted(per_owner[uid] + extra_owner.get(uid, []))
            for uid in owners
        }

        def rebalance_converged():
            for uid in owners:
                tree, crc, count = _owner_state(new_ring.primary(uid), uid)
                if count != len(expected[uid]):
                    return False
            return True

        _await(rebalance_converged, 180, "rebalance + gossip convergence")
        # Zero lost ACKed writes: every ACKed message is present at
        # the owner's (new) primary — exact count per owner, crc per
        # owner recomputable from the known message set.
        for uid in owners:
            want_crc = 0
            k = int(uid[5:])
            for t in expected[uid]:
                want_crc = zlib.crc32(t.encode(), want_crc)
                want_crc = zlib.crc32(b"ct-%d-%s" % (k, t[:29].encode()),
                                      want_crc)
            _tree, crc, count = _owner_state(new_ring.primary(uid), uid)
            assert count == len(expected[uid]), uid
            assert crc == want_crc, f"{uid}: rows diverged after rebalance"
        # Counter-asserted snapshot cutover at the Merkle watermark:
        # every snapshot-installed owner passed the cutover gate
        # (verified = byte-equal to the donor watermark; superset =
        # concurrent gossip rows on top — both safe-to-serve states).
        # An owner a live write reached FIRST drains via gossip
        # instead — designed degradation, not loss — so the >=1
        # install assertion is gated on a QUIET owner having moved
        # (the port-derived ring makes placement run-dependent; a
        # moved-nothing draw is reported, not failed).
        moved_to_new = [uid for uid in owners
                        if new_ring.primary(uid) == new_url]
        # Any placement on the joiner (primary OR replica) installs.
        quiet_moved = [uid for uid in owners
                       if uid not in extra_owner
                       and new_url in new_ring.placement(uid)]
        gain_stats = fleet.stats(new_url)["fleet"]
        if quiet_moved:
            assert gain_stats["rebalanced_owners"] >= 1, gain_stats
        assert (gain_stats["cutovers_verified"]
                + gain_stats["cutovers_superset"]) \
            >= gain_stats["rebalanced_owners"], gain_stats
        leg_rebalance = {
            "joined": new_url,
            "owners_moved": len(moved),
            "moved_to_new_relay": len(moved_to_new),
            "rebalanced_owners": gain_stats["rebalanced_owners"],
            "rebalanced_messages": gain_stats["rebalanced_messages"],
            "cutovers_verified": gain_stats["cutovers_verified"],
            "cutovers_superset": gain_stats["cutovers_superset"],
            "live_writes_acked": sum(acked2.values()),
            "lost_acked_writes": 0,
        }
    finally:
        fleet.stop()

    ratio = fleet_rate / single_rate
    if assert_scaling:
        assert ratio >= 2.0, (
            f"aggregate fleet ingest only {ratio:.2f}x the single relay "
            f"with {args.relays} processes on {cpus} cores"
        )
    print(json.dumps({
        "metric": "fleet_scaling_ratio",
        "value": round(ratio, 2),
        "unit": f"x single-relay ingest ({args.relays} relay processes)",
        "detail": {
            "messages": total,
            "owners": args.owners,
            "zipf_s": args.zipf,
            "batch": args.batch,
            "client_threads": args.threads,
            "cpus": cpus,
            "scaling_asserted": assert_scaling,
            "smoke": bool(args.smoke),
            "single": leg_single,
            "fleet": {"relays": args.relays, "wall_s": round(wall, 3),
                      "msgs_per_s": round(fleet_rate),
                      "byte_identical_to_oracle": True,
                      "strays_outside_placement": 0},
            "rebalance": leg_rebalance,
        },
    }))


if __name__ == "__main__":
    main()
