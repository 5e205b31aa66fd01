"""Snapshot checkpoint & peer bootstrap (server/snapshot.py).

No reference equivalent — the reference relay is a single node that
never cold-starts. These tests pin the subsystem's contracts: the
snapshot wire codec (ValueError only), native-vs-stdlib capture parity
(byte-identical framing), record-aligned crc-checked chunking, the
acceptance scenario — a fresh relay bootstrapping from a donor holding
≥100 owners / ≥10k messages converges BYTE-identically (trees and
tables) in ≥5× fewer HTTP round-trips than pure PR-3 anti-entropy
(counter-asserted) — the golden-parity verify gate (corrupted chunks
and tampered trees abort with live tables untouched), lagging-peer
local-row merge through the XOR gate, watermark handoff to normal
gossip, in-process fetch-interruption resume, SIGKILL-between-chunks
process crash resume without re-transferring completed chunks, and
atomic local checkpoints (write/restore/corruption)."""

import os
import select
import signal
import subprocess
import sys
import time
import urllib.error
import zlib

import pytest

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.server import snapshot
from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
from evolu_tpu.server.replicate import ReplicationManager
from evolu_tpu.sync import protocol
from evolu_tpu.sync.client import _http_post

BASE = 1_700_000_000_000
MINUTE = 60_000
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _msgs(node, minute, start, n, payload=b""):
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(
                Timestamp(BASE + minute * MINUTE + (start + i) * 500, 0, node)
            ),
            b"ct\x00-%d-%d" % (minute, start + i) + payload,
        )
        for i in range(n)
    )


def _fast_post(url, body):
    return _http_post(url, body, retries=0)


def _state(store):
    """Byte-level replica state: per owner, the STORED tree text and
    every message row — what must be identical after a bootstrap."""
    return {
        u: (store.get_merkle_tree_string(u), store.replica_messages(u, ""))
        for u in sorted(store.user_ids())
    }


def _seed(store, owners, per_minute, minutes, payload=b""):
    for i in range(owners):
        node = f"{i + 1:016x}"
        for m in range(minutes):
            store.add_messages(
                f"owner{i:03d}", _msgs(node, m, 0, per_minute, payload)
            )


def _round_trips(replica_id):
    return sum(
        metrics.get_counter("evolu_repl_round_trips_total",
                            replica=replica_id, leg=leg)
        for leg in ("summary", "pull", "snapshot", "snapshot/chunk")
    )


# -- wire codec --


def _codec_vectors():
    manifest = protocol.SnapshotManifest(
        "snap-1", (100, 7), (0xDEADBEEF, 0), (("alice", -123456, 42),
                                              ("b\x00ob", 0, 0xFFFFFFFF)),
        12345, 107,
    )
    req = protocol.SnapshotRequest("replica-9", 1 << 20)
    creq = protocol.SnapshotChunkRequest("snap-1", 3, "replica-9")
    chunk = protocol.SnapshotChunk("snap-1", 3, 0xCAFEBABE, b"\x00\xffpayload")
    return manifest, req, creq, chunk


def test_snapshot_wire_codec_round_trips():
    manifest, req, creq, chunk = _codec_vectors()
    assert protocol.decode_snapshot_manifest(
        protocol.encode_snapshot_manifest(manifest)) == manifest
    assert protocol.decode_snapshot_request(
        protocol.encode_snapshot_request(req)) == req
    assert protocol.decode_snapshot_chunk_request(
        protocol.encode_snapshot_chunk_request(creq)) == creq
    assert protocol.decode_snapshot_chunk(
        protocol.encode_snapshot_chunk(chunk)) == chunk


def test_snapshot_wire_decoders_raise_valueerror_only():
    """The wire-decoder invariant applies to the snapshot codec: ANY
    malformed input raises ValueError only."""
    import random

    manifest, req, creq, chunk = _codec_vectors()
    valid = [
        protocol.encode_snapshot_manifest(manifest),
        protocol.encode_snapshot_request(req),
        protocol.encode_snapshot_chunk_request(creq),
        protocol.encode_snapshot_chunk(chunk),
    ]
    rng = random.Random(11)
    cases = [b"\xff", b"\x08", b"\x0a\x05ab", b"\x08\x01",
             b"\x0d\x01\x02\x03\x04", b"\x22\x02\x08\x01"]
    for blob in valid:
        cases.extend(blob[:k] for k in range(1, len(blob), 5))
        for _ in range(40):
            b = bytearray(blob)
            b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
            cases.append(bytes(b))
        cases.extend(bytes(rng.randrange(256) for _ in range(n)) for n in (3, 17, 64))
    decoders = (
        protocol.decode_snapshot_manifest,
        protocol.decode_snapshot_request,
        protocol.decode_snapshot_chunk_request,
        protocol.decode_snapshot_chunk,
    )
    for dec in decoders:
        for data in cases:
            try:
                dec(bytes(data))
            except ValueError:
                pass  # the ONLY sanctioned error type


# -- capture + framing --


def test_capture_native_matches_python_oracle():
    """The one-C-call capture leg frames byte-identically to the
    stdlib SQL oracle — including NUL-bearing contents and multiple
    owners across minutes."""
    from evolu_tpu.storage.native import native_available

    if not native_available():
        pytest.skip("native host library unavailable")
    nat, py = RelayStore(backend="native"), RelayStore(backend="python")
    for s in (nat, py):
        _seed(s, owners=5, per_minute=9, minutes=3)
    with nat.db.transaction():
        raw_native = snapshot.capture_shard(nat.db)
        raw_oracle = snapshot._capture_shard_py(nat.db)
    with py.db.transaction():
        raw_py = snapshot.capture_shard(py.db)
    assert raw_native == raw_oracle == raw_py
    nat.close()
    py.close()


def test_chunks_split_at_record_boundaries_and_reassemble():
    store = RelayStore()
    _seed(store, owners=4, per_minute=20, minutes=2)
    manifest, chunks = snapshot.capture_snapshot(store, chunk_bytes=300)
    assert len(chunks) > 3
    assert b"".join(chunks) == b"".join(chunks)  # sanity
    for c, size, crc in zip(chunks, manifest.chunk_sizes, manifest.chunk_crcs):
        assert len(c) == size
        assert zlib.crc32(c) == crc
        list(snapshot.iter_records(c))  # every chunk parses standalone
    recs = [r for c in chunks for r in snapshot.iter_records(c)]
    assert sum(1 for r in recs if r[0] == "M") == manifest.message_count == 160
    assert sum(1 for r in recs if r[0] == "T") == len(manifest.owners) == 4
    store.close()


# -- the acceptance scenario --


def test_fresh_peer_bootstrap_beats_anti_entropy_5x_in_round_trips():
    """A fresh relay bootstrapping from a donor holding 128 owners /
    12,288 messages converges byte-identically (trees AND tables), in
    ≥5× fewer HTTP round-trips than pure PR-3 anti-entropy under the
    donor's configured serve_pull caps (constructor args — satellite).
    Round-trips are counter-asserted on the puller's transport leg
    counter, byte-identity on full stored state."""
    donor_store = ShardedRelayStore(shards=2)
    _seed(donor_store, owners=128, per_minute=12, minutes=8)
    donor_mgr = ReplicationManager(
        donor_store, [], replica_id="accept-donor",
        pull_messages_per_owner=64, pull_messages_per_response=512,
    )
    donor = RelayServer(donor_store, replication=donor_mgr).start()
    try:
        donor_state = _state(donor_store)
        assert len(donor_state) == 128
        assert sum(len(rows) for _t, rows in donor_state.values()) == 12288

        # Leg A: pure anti-entropy (bootstrap disabled — the PR-3 path).
        dest_a = RelayStore()
        mgr_a = ReplicationManager(
            dest_a, [donor.url], replica_id="accept-anti", http_post=_fast_post,
        )
        for _ in range(200):
            mgr_a.run_once()
            if _state(dest_a) == donor_state:
                break
        assert _state(dest_a) == donor_state, "anti-entropy never converged"
        anti_rts = _round_trips("accept-anti")

        # Leg B: snapshot bootstrap.
        dest_b = RelayStore()
        mgr_b = ReplicationManager(
            dest_b, [donor.url], replica_id="accept-snap", http_post=_fast_post,
            bootstrap_lag_owners=8, snapshot_chunk_bytes=512 * 1024,
        )
        mgr_b.run_once()  # bootstrap
        mgr_b.run_once()  # post-watermark gossip round (verifies converged)
        assert _state(dest_b) == donor_state, "bootstrap state diverged"
        snap_rts = _round_trips("accept-snap")

        assert snap_rts * 5 <= anti_rts, (snap_rts, anti_rts)
        # The snapshot leg moved ZERO ranged-pull messages — the whole
        # history rode the chunk stream.
        assert metrics.get_counter(
            "evolu_repl_messages_pulled_total",
            replica="accept-snap", peer=donor.url,
        ) == 0
        assert metrics.get_counter(
            "evolu_snap_installs_total", result="ok",
            replica="accept-snap", peer=donor.url,
        ) == 1
        mgr_a.stop()
        mgr_b.stop()
        dest_a.close()
        dest_b.close()
    finally:
        donor.stop()


def test_bootstrap_hands_off_to_gossip_at_the_watermark():
    """Writes landing on the donor AFTER the snapshot was captured
    arrive through normal anti-entropy, and the pull counter shows the
    tail ONLY — the watermark contract."""
    donor_store = RelayStore()
    _seed(donor_store, owners=12, per_minute=10, minutes=2)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = RelayStore()
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="wm-peer", http_post=_fast_post,
        bootstrap_lag_owners=4,
    )
    try:
        mgr.run_once()
        assert _state(dest) == _state(donor_store)
        # Post-snapshot tail: 17 fresh rows on one owner.
        donor_store.add_messages("owner003", _msgs("4" * 16, 30, 0, 17))
        mgr.run_once()
        assert _state(dest) == _state(donor_store)
        assert metrics.get_counter(
            "evolu_repl_messages_pulled_total", replica="wm-peer", peer=donor.url
        ) == 17
        # Routine fleet growth stays incremental: ONE new owner on the
        # donor must ride a ranged pull, never a full re-bootstrap —
        # even at bootstrap_lag_owners=4 with unknown(1) < majority.
        donor_store.add_messages("brand-new-owner", _msgs("9" * 16, 31, 0, 6))
        mgr.run_once()
        assert _state(dest) == _state(donor_store)
        assert metrics.get_counter(
            "evolu_snap_installs_total", result="ok",
            replica="wm-peer", peer=donor.url,
        ) == 1, "a single new owner re-triggered a full snapshot bootstrap"
        assert metrics.get_counter(
            "evolu_repl_messages_pulled_total", replica="wm-peer", peer=donor.url
        ) == 23
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


def test_lagging_peer_bootstrap_merges_local_only_rows():
    """A lagging (NOT empty) peer keeps rows the donor never had: they
    merge into the installed snapshot through the changes==1 XOR gate,
    so the swapped-in trees are exact unions (recomputable from the
    swapped-in tables)."""
    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_to_string, minute_deltas_host,
    )

    donor_store = RelayStore()
    _seed(donor_store, owners=20, per_minute=8, minutes=2)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = RelayStore()
    # The lagging peer holds an OLD subset of one donor owner (same
    # node id → identical timestamps → true subset) plus a local-only
    # owner and local-only rows the donor lacks entirely.
    dest.add_messages("owner001", _msgs(f"{2:016x}", 0, 0, 8))
    local_only = _msgs("e" * 16, 40, 0, 5)
    dest.add_messages("owner001", local_only)
    dest.add_messages("local-owner", _msgs("f" * 16, 41, 0, 3))
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="lag-peer", http_post=_fast_post,
        bootstrap_lag_owners=4,
    )
    try:
        mgr.run_once()
        got = _state(dest)
        donor_state = _state(donor_store)
        # Donor rows all present; local-only rows survived the swap.
        assert set(got) == set(donor_state) | {"local-owner"}
        assert len(got["owner001"][1]) == len(donor_state["owner001"][1]) + 5
        assert len(got["local-owner"][1]) == 3
        # Every swapped-in tree is exactly the recompute of its rows.
        for uid, (tree_text, rows) in got.items():
            deltas, _d = minute_deltas_host([m.timestamp for m in rows])
            assert tree_text == merkle_tree_to_string(
                apply_prefix_xors({}, deltas)), uid
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


# -- integrity gates --


def _corrupting_post(flip_in_chunks=True):
    """Transport that flips one payload bit in every chunk response."""

    def post(url, body):
        out = _fast_post(url, body)
        if flip_in_chunks and url.endswith("/replicate/snapshot/chunk"):
            chunk = protocol.decode_snapshot_chunk(out)
            bad = bytearray(chunk.payload)
            bad[len(bad) // 2] ^= 0x40
            out = protocol.encode_snapshot_chunk(
                protocol.SnapshotChunk(
                    chunk.snapshot_id, chunk.index, chunk.crc, bytes(bad)
                )
            )
        return out

    return post


def test_corrupted_chunk_aborts_install_live_tables_untouched():
    donor_store = RelayStore()
    _seed(donor_store, owners=6, per_minute=10, minutes=2)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = RelayStore()
    dest.add_messages("pre-existing", _msgs("a" * 16, 0, 0, 4))
    before = _state(dest)
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="corrupt-peer",
        http_post=_corrupting_post(), bootstrap_lag_owners=1,
    )
    try:
        with pytest.raises(snapshot.SnapshotInstallError):
            mgr.bootstrap_from(donor.url)
        assert _state(dest) == before  # live tables untouched
        # Install state dropped: nothing to resume from.
        assert snapshot.SnapshotInstaller(dest).pending() is None
        assert metrics.get_counter(
            "evolu_snap_installs_total", result="error",
            replica="corrupt-peer", peer=donor.url,
        ) >= 1
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


def test_verify_rejects_tampered_tree_byte_identity():
    """The golden-parity gate: a snapshot whose shipped tree text is
    NOT byte-identical to the recompute from its own rows aborts, even
    when manifest digests are made to agree with the tampered text."""
    store = RelayStore()
    _seed(store, owners=3, per_minute=6, minutes=2)
    manifest, chunks = snapshot.capture_snapshot(store)
    stream = b"".join(chunks)
    recs = list(snapshot.iter_records(stream))
    # Tamper one owner's TREE text (flip a hash digit), rebuild the
    # stream AND a consistent manifest (crc/root updated to the
    # tampered text — only byte-recompute parity can catch it).
    out = []
    tampered_uid = None
    for r in recs:
        if r[0] == "T" and tampered_uid is None:
            from evolu_tpu.core.merkle import (
                merkle_tree_from_string, merkle_tree_to_string,
            )
            from evolu_tpu.core.murmur import to_int32

            tampered_uid = r[1]
            t = merkle_tree_from_string(r[2])
            t["hash"] = to_int32((t.get("hash") or 0) ^ 1)
            bad_tree = merkle_tree_to_string(t)
            out.append(snapshot._frame_tree(r[1], bad_tree))
            owners = tuple(
                (u, merkle_tree_from_string(bad_tree).get("hash") or 0,
                 zlib.crc32(bad_tree.encode())) if u == r[1] else (u, rh, tc)
                for u, rh, tc in manifest.owners
            )
        elif r[0] == "T":
            out.append(snapshot._frame_tree(r[1], r[2]))
        else:
            out.append(snapshot._frame_message(r[1], r[2], r[3]))
    bad_stream = b"".join(out)
    bad_manifest = protocol.SnapshotManifest(
        manifest.snapshot_id, (len(bad_stream),), (zlib.crc32(bad_stream),),
        owners, manifest.message_count, len(bad_stream),
    )
    dest = RelayStore()
    with pytest.raises(snapshot.SnapshotInstallError):
        snapshot.install_stream(dest, bad_manifest, [bad_stream])
    assert dest.user_ids() == []
    store.close()
    dest.close()


# -- resume --


class _FlakyTransport:
    """Fails every chunk leg after the first `allow` with a
    connection-level error — an interrupted bootstrap."""

    def __init__(self, allow):
        self.allow = allow
        self.chunk_posts = 0
        self.failing = True

    def post(self, url, body):
        if url.endswith("/replicate/snapshot/chunk"):
            if self.failing and self.chunk_posts >= self.allow:
                raise urllib.error.URLError("flaky (fault injection)")
            self.chunk_posts += 1
        return _fast_post(url, body)


def test_interrupted_fetch_resumes_from_persisted_watermark():
    """A bootstrap cut off mid-fetch resumes at the NEXT round from
    the persisted chunk watermark: completed chunks are not
    re-requested (donor-side per-index serve log), and the final state
    is byte-identical."""
    donor_store = RelayStore()
    _seed(donor_store, owners=10, per_minute=40, minutes=5, payload=b"x" * 40)
    donor = RelayServer(donor_store, peers=[]).start()
    served: list = []
    cache = donor.replication.snapshot_cache
    orig_chunk = cache.chunk
    cache.chunk = lambda sid, i: (served.append(i), orig_chunk(sid, i))[1]
    dest = RelayStore()
    flaky = _FlakyTransport(allow=2)
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="resume-peer", http_post=flaky.post,
        bootstrap_lag_owners=1, snapshot_chunk_bytes=64 * 1024,
    )
    try:
        with pytest.raises(urllib.error.URLError):
            mgr.bootstrap_from(donor.url)
        pending = snapshot.SnapshotInstaller(dest).pending()
        assert pending is not None and pending["next_chunk"] == 2
        assert len(pending["manifest"].chunk_sizes) > 3
        flaky.failing = False
        mgr.bootstrap_from(donor.url)  # resumes — no restart
        assert _state(dest) == _state(donor_store)
        # Chunks 0 and 1 were served exactly once each: the resume
        # started at the watermark, not at zero.
        assert served.count(0) == 1 and served.count(1) == 1, served
        assert metrics.get_counter(
            "evolu_snap_resumes_total", replica="resume-peer", peer=donor.url
        ) == 1
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


def test_multi_peer_resume_sticks_to_the_original_donor():
    """In a multi-peer mesh, the first round after a crash may target a
    DIFFERENT peer than the one the persisted watermark came from; the
    resume must redirect to the original donor (only it still serves
    the snapshot id) instead of discarding completed chunks."""
    donor_store = RelayStore()
    _seed(donor_store, owners=10, per_minute=40, minutes=5, payload=b"m" * 40)
    donor = RelayServer(donor_store, peers=[]).start()
    decoy_store = RelayStore()
    _seed(decoy_store, owners=2, per_minute=4, minutes=1)
    decoy = RelayServer(decoy_store, peers=[]).start()
    decoy_chunks: list = []
    dc = decoy.replication.snapshot_cache
    orig_dc = dc.chunk
    dc.chunk = lambda sid, i: (decoy_chunks.append(i), orig_dc(sid, i))[1]
    donor_served: list = []
    cache = donor.replication.snapshot_cache
    orig_chunk = cache.chunk
    cache.chunk = lambda sid, i: (donor_served.append(i), orig_chunk(sid, i))[1]
    dest = RelayStore()
    flaky = _FlakyTransport(allow=2)
    mgr = ReplicationManager(
        dest, [decoy.url, donor.url], replica_id="multi-peer",
        http_post=flaky.post, bootstrap_lag_owners=1,
        snapshot_chunk_bytes=64 * 1024,
    )
    try:
        with pytest.raises(urllib.error.URLError):
            mgr.bootstrap_from(donor.url)  # interrupted after 2 chunks
        flaky.failing = False
        # "Restart": the next round happens to target the DECOY peer.
        mgr.bootstrap_from(decoy.url)
        assert _state(dest) == _state(donor_store)  # donor's data, not decoy's
        assert donor_served.count(0) == 1 and donor_served.count(1) == 1
        assert not decoy_chunks, "resume refetched from the wrong peer"
    finally:
        mgr.stop()
        donor.stop()
        decoy.stop()
        dest.close()


def test_stranded_mid_swap_install_finishes_on_the_next_round():
    """A crash BETWEEN shard swaps leaves a verified install half
    swapped in; the half-swapped live tables may advertise enough
    owners that the bootstrap trigger never fires again — any
    manager's first round must finish the pending swap regardless."""
    donor_store = RelayStore()
    _seed(donor_store, owners=10, per_minute=8, minutes=2)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = ShardedRelayStore(shards=2)
    try:
        # Reproduce the crash state by driving the installer directly:
        # full fetch + verify, phase=swap persisted, only shard 0
        # actually swapped (the process "died" before shard 1).
        manifest, chunks = snapshot.capture_snapshot(donor_store)
        inst = snapshot.SnapshotInstaller(dest)
        inst.begin(manifest, donor.url)
        for i, payload in enumerate(chunks):
            inst.install_chunk(i, payload, expected_crc=manifest.chunk_crcs[i])
        inst.verify(manifest)
        inst._state_set(phase="swap")
        db = dest.shards[0].db
        with snapshot._exclusive_txn(db):
            db.run('DROP TABLE "message"')
            db.run('ALTER TABLE "messageBsnap" RENAME TO "message"')
            db.run('DROP TABLE "merkleTree"')
            db.run('ALTER TABLE "merkleTreeBsnap" RENAME TO "merkleTree"')
        assert _state(dest) != _state(donor_store)  # half swapped

        # "Restart": a fresh manager whose threshold will NOT re-arm
        # bootstrap (shard 0's owners are already visible) still
        # finishes the pending swap on its first round.
        mgr = ReplicationManager(
            dest, [donor.url], replica_id="strand-peer", http_post=_fast_post,
            bootstrap_lag_owners=50,
        )
        mgr.run_once()
        assert _state(dest) == _state(donor_store)
        assert snapshot.SnapshotInstaller(dest).pending() is None
        mgr.stop()
    finally:
        donor.stop()
        dest.close()


def test_expired_snapshot_restarts_fresh():
    """A donor that no longer serves the snapshot id (cache expiry /
    restart) answers 400 on the chunk leg: the puller drops its stale
    watermark and the next attempt bootstraps fresh to byte-identity."""
    donor_store = RelayStore()
    _seed(donor_store, owners=8, per_minute=30, minutes=3, payload=b"y" * 40)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = RelayStore()
    flaky = _FlakyTransport(allow=1)
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="expire-peer", http_post=flaky.post,
        bootstrap_lag_owners=1, snapshot_chunk_bytes=64 * 1024,
    )
    try:
        with pytest.raises(urllib.error.URLError):
            mgr.bootstrap_from(donor.url)
        donor.replication.snapshot_cache._entries.clear()  # donor "restarted"
        flaky.failing = False
        with pytest.raises(urllib.error.HTTPError):  # 400 → state dropped
            mgr.bootstrap_from(donor.url)
        assert snapshot.SnapshotInstaller(dest).pending() is None
        mgr.bootstrap_from(donor.url)  # fresh bootstrap succeeds
        assert _state(dest) == _state(donor_store)
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


def _read_lines_until(proc, predicate, deadline_s):
    """Read child stdout lines until predicate(line) or deadline."""
    deadline = time.time() + deadline_s
    lines = []
    while time.time() < deadline:
        r, _w, _x = select.select([proc.stdout], [], [], 0.1)
        if not r:
            if proc.poll() is not None:
                break
            continue
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line.strip())
        if predicate(line):
            return lines
    return lines


def test_sigkill_between_chunks_resumes_from_watermark(tmp_path):
    """The satellite crash test: SIGKILL the bootstrapping relay
    PROCESS between snapshot chunks, restart it, and the install
    resumes from the persisted watermark — completed chunks are not
    re-transferred (donor-side per-index serve log) and the final
    trees/tables are byte-identical to the donor's."""
    donor_store = RelayStore()
    _seed(donor_store, owners=8, per_minute=50, minutes=4, payload=b"z" * 48)
    donor = RelayServer(donor_store, peers=[]).start()
    served: list = []
    cache = donor.replication.snapshot_cache
    orig_chunk = cache.chunk
    cache.chunk = lambda sid, i: (served.append(i), orig_chunk(sid, i))[1]

    donor_crc = 0
    for u in sorted(donor_store.user_ids()):
        donor_crc = zlib.crc32(donor_store.get_merkle_tree_string(u).encode(), donor_crc)
        for m in donor_store.replica_messages(u, ""):
            donor_crc = zlib.crc32(m.timestamp.encode(), donor_crc)
            donor_crc = zlib.crc32(m.content, donor_crc)

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["JAX_PLATFORMS"] = "cpu"
    db_path = str(tmp_path / "victim.db")
    worker = os.path.join(_REPO, "tests", "_snapshot_bootstrap_worker.py")

    try:
        # Run 1: slow installs; SIGKILL after the chunk-1 watermark
        # commits (the CHUNK line prints post-commit, then sleeps).
        p1 = subprocess.Popen(
            [sys.executable, worker, donor.url, db_path, "0.4"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        lines = _read_lines_until(p1, lambda ln: "CHUNK 1" in ln, 60)
        assert any("CHUNK 1" in ln for ln in lines), lines
        os.kill(p1.pid, signal.SIGKILL)
        p1.wait(timeout=10)
        completed_before_kill = sum(1 for ln in lines if ln.startswith("CHUNK"))
        serves_before_kill = list(served)
        assert completed_before_kill >= 2

        # Run 2: fresh process over the same DB file — must resume.
        p2 = subprocess.Popen(
            [sys.executable, worker, donor.url, db_path, "0"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        lines2 = _read_lines_until(p2, lambda ln: ln.startswith("DONE"), 120)
        p2.wait(timeout=10)
        done = [ln for ln in lines2 if ln.startswith("DONE")]
        assert done, lines2
        assert done[0] == f"DONE crc={donor_crc:08x}"  # byte-identical end state

        # Resume, not restart: the second run's chunk requests start at
        # the persisted watermark — every chunk completed before the
        # kill was transferred exactly once across both runs.
        run2_serves = served[len(serves_before_kill):]
        assert run2_serves, "second run never fetched (no resume?)"
        assert min(run2_serves) >= completed_before_kill, (
            serves_before_kill, run2_serves, completed_before_kill,
        )
        for i in range(completed_before_kill):
            assert served.count(i) == 1, (i, served)
    finally:
        donor.stop()


def test_client_write_accepted_mid_install_survives_the_swap(monkeypatch):
    """A write the relay ACKs while a bootstrap install is in flight
    must not vanish when the side tables swap in: the swap transaction
    re-merges live rows through the XOR gate before the rename
    (review finding — the merge used to run before the swap, leaving
    a drop window)."""
    import threading

    donor_store = RelayStore()
    _seed(donor_store, owners=8, per_minute=40, minutes=4, payload=b"w" * 40)
    donor = RelayServer(donor_store, peers=[]).start()
    dest = RelayStore()
    orig = snapshot.SnapshotInstaller.install_chunk

    def slow(self, i, p, expected_crc=None):
        n = orig(self, i, p, expected_crc)
        time.sleep(0.15)
        return n

    monkeypatch.setattr(snapshot.SnapshotInstaller, "install_chunk", slow)
    mgr = ReplicationManager(
        dest, [donor.url], replica_id="midwrite-peer", http_post=_fast_post,
        bootstrap_lag_owners=1, snapshot_chunk_bytes=64 * 1024,
    )
    try:
        t = threading.Thread(target=lambda: mgr.bootstrap_from(donor.url))
        t.start()
        time.sleep(0.2)  # mid-install: the relay ACKs a client write
        dest.add_messages("mid-install-owner", _msgs("d" * 16, 99, 0, 3))
        t.join(timeout=60)
        assert not t.is_alive()
        got = _state(dest)
        assert len(got.get("mid-install-owner", ("", ()))[1]) == 3, (
            "acknowledged mid-install write vanished in the swap"
        )
        donor_state = _state(donor_store)
        assert all(got[u] == donor_state[u] for u in donor_state)
    finally:
        mgr.stop()
        donor.stop()
        dest.close()


def test_capture_waits_out_foreign_open_transactions():
    """The batch engine's explicit begin/commit protocol releases the
    db lock between statements; a capture (or install/swap) landing
    mid-batch must WAIT for the commit, never join the foreign
    transaction — joining would snapshot uncommitted rows (or commit
    half a swap with someone else's batch)."""
    import threading

    from evolu_tpu.storage.native import native_available

    if not native_available():
        pytest.skip("explicit begin/commit lives on the native backend")
    store = RelayStore(backend="native")
    _seed(store, owners=2, per_minute=5, minutes=1)
    db = store.db
    db.begin()  # the engine's shard-parallel ingest shape
    db.run(
        'INSERT INTO "message" ("timestamp", "userId", "content") '
        "VALUES (?, ?, ?)",
        ("t" * 46, "owner000", b"mid-batch"),
    )
    result = {}
    t = threading.Thread(
        target=lambda: result.update(m=snapshot.capture_snapshot(store)[0])
    )
    t.start()
    time.sleep(0.25)
    assert t.is_alive(), "capture joined a foreign open transaction"
    db.commit()
    t.join(timeout=10)
    assert not t.is_alive()
    # The capture ran AFTER the commit: it sees the committed batch,
    # all 11 rows — never a torn mid-transaction view.
    assert result["m"].message_count == 11
    store.close()


# -- local checkpoints --


def test_checkpoint_write_restore_byte_identical(tmp_path):
    src = ShardedRelayStore(shards=2)
    _seed(src, owners=9, per_minute=11, minutes=3)
    path = str(tmp_path / "relay.checkpoint")
    snapshot.write_checkpoint(src, path)
    assert not os.path.exists(path + ".tmp")  # atomic: tmp renamed away

    # Restore into a DIFFERENT sharding layout: rows re-route by owner.
    dest = ShardedRelayStore(shards=4)
    snapshot.restore_checkpoint(dest, path)
    assert _state(dest) == _state(src)

    # Corruption is detected before anything installs.
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - 20)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes((b[0] ^ 0x10,)))
    fresh = RelayStore()
    with pytest.raises(ValueError):
        snapshot.restore_checkpoint(fresh, path)
    assert fresh.user_ids() == []
    src.close()
    dest.close()
    fresh.close()


def test_periodic_checkpointer_via_relay_server(tmp_path):
    path = str(tmp_path / "live.checkpoint")
    store = RelayStore()
    _seed(store, owners=3, per_minute=5, minutes=1)
    server = RelayServer(store, checkpoint_interval_s=0.05,
                         checkpoint_path=path).start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not os.path.exists(path):
            time.sleep(0.02)
        assert os.path.exists(path), "periodic checkpoint never written"
    finally:
        server.stop()
    restored = RelayStore()
    snapshot.restore_checkpoint(restored, path)
    assert sorted(restored.user_ids()) == ["owner000", "owner001", "owner002"]
    restored.close()


def test_relay_server_requires_checkpoint_path_for_memory_stores():
    with pytest.raises(ValueError):
        RelayServer(RelayStore(), checkpoint_interval_s=1.0)


def test_config_defaults_flow_into_the_replication_manager():
    """utils/config.py fleet knobs are LIVE process defaults: any
    constructor arg left at None resolves from default_config."""
    from evolu_tpu.utils.config import Config, default_config, set_config

    old = default_config
    store = RelayStore()
    try:
        set_config(Config(pull_messages_per_owner=77,
                          pull_messages_per_response=555,
                          bootstrap_lag_owners=5))
        mgr = ReplicationManager(store, [], replica_id="cfg-peer")
        assert mgr.pull_messages_per_owner == 77
        assert mgr.pull_messages_per_response == 555
        assert mgr.bootstrap_lag_owners == 5
        # Explicit constructor args still win over the config.
        mgr2 = ReplicationManager(store, [], replica_id="cfg-peer2",
                                  pull_messages_per_owner=11)
        assert mgr2.pull_messages_per_owner == 11
        mgr.stop()
        mgr2.stop()
    finally:
        set_config(old)
        store.close()


# -- observability surface --


def test_snapshot_stats_and_metrics_surface():
    import json
    import urllib.request

    donor_store = RelayStore()
    _seed(donor_store, owners=5, per_minute=6, minutes=1)
    donor = RelayServer(donor_store, peers=[]).start()
    dest_store = RelayStore()
    dest = RelayServer(
        dest_store, peers=[donor.url], replication_interval_s=3600,
        bootstrap_lag_owners=1,
    ).start()
    try:
        deadline = time.time() + 20
        while time.time() < deadline and _state(dest_store) != _state(donor_store):
            time.sleep(0.05)
        assert _state(dest_store) == _state(donor_store)
        with urllib.request.urlopen(dest.url + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        (peer,) = stats["replication"]["peers"]
        assert peer["snapshot_bootstraps"] >= 1
        assert peer["snapshot_chunks_fetched"] >= 1
        assert peer["snapshot_bytes_fetched"] > 0
        with urllib.request.urlopen(donor.url + "/stats", timeout=10) as r:
            donor_stats = json.loads(r.read())
        snap = donor_stats["replication"]["snapshot"]
        assert snap["captures"] >= 1
        assert snap["chunks_served"] >= 1
        assert snap["capture_rows"] >= 30
        with urllib.request.urlopen(donor.url + "/metrics", timeout=10) as r:
            prom = r.read().decode()
        assert "evolu_snap_captures_total" in prom
        assert "evolu_snap_chunks_served_total" in prom
    finally:
        dest.stop()
        donor.stop()
