"""Seeded generators for every cell: the benchmark's own copies.

Copied from `benchmarks/config3_server_reconcile.py` (`_ciphertext_pool`,
`build_requests`) and `chip_smoke.py` (the pushed-round shape), with sizes
from the configuration file and the seed from `--seed` instead of
environment variables. Module level imports nothing of JAX: `perf/loadgen.py` runs
`PushClient` in child processes that must never touch the chip.
"""

import struct
import urllib.request

from evolu_tpu.core.merkle import (
    apply_prefix_xors, merkle_tree_from_string, merkle_tree_to_string,
    minute_deltas_host)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.sync import protocol

# Every ciphertext is encrypted under this mnemonic (the relay is
# E2EE-blind; a client that cold-syncs one of these owners decrypts with
# it).
MNEMONIC = "legal winner thank year wave sausage worth useful legal winner thank yellow"
BASE_MILLIS = 1_700_000_000_000


def ciphertext_pool(size: int) -> tuple:
    """REAL OpenPGP ciphertexts of realistic CrdtMessageContents: the
    relay never reads content, but its bytes shape storage and the wire."""
    from evolu_tpu.core.types import CrdtMessage
    from evolu_tpu.sync.client import encrypt_messages

    msgs = tuple(
        CrdtMessage("t", "todo", f"Tf9faXx1ryRXmPF6e_{i:04d}", "title", f"item {i} ✓")
        for i in range(size))
    return tuple(e.content for e in encrypt_messages(msgs, MNEMONIC))


def write_pool(path: str, pool) -> None:
    with open(path, "wb") as f:
        for c in pool:
            f.write(struct.pack("<I", len(c)))
            f.write(c)


def read_pool(path: str) -> tuple:
    with open(path, "rb") as f:
        data = f.read()
    out, pos = [], 0
    while pos < len(data):
        (n,) = struct.unpack_from("<I", data, pos)
        out.append(data[pos + 4:pos + 4 + n])
        pos += 4 + n
    return tuple(out)


def http_post(url: str, body: bytes, timeout_s: float) -> bytes:
    """One new connection a request and no retry, as a plain client makes
    them (`sync.client._http_post` without its backoff): any status but
    200 raises."""
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return resp.read()


def owner_id(o: int) -> str:
    return f"owner{o:04d}"


def murmur3_32_rows(rows):
    """MurmurHash3 x86 32-bit, seed 0, of every row of an (n, width)
    uint8 matrix at once → (n,) uint32. The benchmark's own plain copy of
    the reference's timestamp hash (npm murmurhash@2.0.1 v3): a million
    hashes in one numpy pass, where the program's pure-Python fold takes
    half a minute. `perf/selfcheck.py` holds it to the reference's golden
    value and to `core.murmur` on seeded rows."""
    import numpy as np

    n, width = rows.shape
    c1, c2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)

    def rotl(x, r):
        return (x << np.uint32(r)) | (x >> np.uint32(32 - r))

    def mix(k):
        return rotl(k * c1, 15) * c2

    h = np.zeros(n, np.uint32)
    body = width & ~3
    blocks = rows[:, :body].reshape(n, body // 4, 4).astype(np.uint32)
    words = (blocks[:, :, 0] | (blocks[:, :, 1] << np.uint32(8))
             | (blocks[:, :, 2] << np.uint32(16)) | (blocks[:, :, 3] << np.uint32(24)))
    for j in range(body // 4):
        h = rotl(h ^ mix(words[:, j]), 13) * np.uint32(5) + np.uint32(0xE6546B64)
    tail = rows[:, body:].astype(np.uint32)
    if tail.shape[1]:
        k = np.zeros(n, np.uint32)
        for j in range(tail.shape[1]):
            k |= tail[:, j] << np.uint32(8 * j)
        h ^= mix(k)
    h ^= np.uint32(width)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def _ascii_rows(strings, width: int):
    import numpy as np

    return np.frombuffer("".join(strings).encode("ascii"), np.uint8).reshape(-1, width)


def build_requests(n: int, owners: int, seed: int, pool) -> list:
    """The preload: `n` messages spread by `seed` over `owners` owners,
    one steady-state SyncRequest per owner (the client's tree already
    covers what it pushes, so the relay answers empty). Message i has
    millis BASE + i // 16, counter i % 16 and a node of its owner (the
    shape of benchmarks/config3's generator), built as one (n, 46) byte
    matrix. The tree is the host fold of the owner's timestamps: per
    (owner, minute) the XOR of the murmur3 hashes, applied by
    `core.merkle.apply_prefix_xors`. It is the plain reference every
    stored tree is held to."""
    import numpy as np

    from evolu_tpu.core.merkle import minutes_base3
    from evolu_tpu.core.timestamp import millis_to_iso

    rng = np.random.default_rng(seed)
    owner = rng.integers(0, owners, n)
    digit = rng.integers(0, 16, n)
    i = np.arange(n)
    millis = BASE_MILLIS + i // 16
    hex_lower = np.frombuffer(b"0123456789abcdef", np.uint8)
    hex_upper = np.frombuffer(b"0123456789ABCDEF", np.uint8)
    rows = np.empty((n, 46), np.uint8)
    iso = _ascii_rows([millis_to_iso(m) for m in range(BASE_MILLIS, int(millis[-1]) + 1)], 24)
    rows[:, :24] = iso[i // 16]
    rows[:, 24:28] = np.frombuffer(b"-000", np.uint8)
    rows[:, 28] = hex_upper[i % 16]
    rows[:, 29] = ord("-")
    rows[:, 30:45] = _ascii_rows([f"{o:015x}" for o in range(owners)], 15)[owner]
    rows[:, 45] = hex_lower[digit]
    stamps = rows.reshape(-1).view("S46").astype("U46").tolist()
    hashes = murmur3_32_rows(rows)

    # Group by owner, keeping message order inside each owner.
    order = np.argsort(owner, kind="stable")
    bounds = np.flatnonzero(np.diff(owner[order])) + 1
    minute = millis // 60_000
    requests = []
    n_pool = len(pool)
    for ix in np.split(order, bounds):
        deltas = {}
        for m in np.unique(minute[ix]):
            x = int(np.bitwise_xor.reduce(hashes[ix][minute[ix] == m]))
            deltas[minutes_base3(int(m) * 60_000)] = x - (1 << 32) if x >= 1 << 31 else x
        tree = merkle_tree_to_string(apply_prefix_xors({}, deltas))
        msgs = tuple(protocol.EncryptedCrdtMessage(stamps[j], pool[j % n_pool])
                     for j in ix.tolist())
        requests.append(protocol.SyncRequest(msgs, owner_id(int(owner[ix[0]])), "f" * 16, tree))
    return requests


class PushClient:
    """One closed-loop client of one owner: each round pushes `msgs` new
    messages (pool ciphertexts, fresh monotonic timestamps on its own
    node id) with its own post-apply tree, folded on the host as it
    goes. Everything a round holds follows from (slot, round number), so
    the parent regenerates every acknowledged timestamp from the count."""

    def __init__(self, slot: int, owner: str, tree: str, base_millis: int,
                 msgs: int, pool, kind: str = "c"):
        self.slot = slot
        self.owner = owner
        self.node = f"{'0' * 11}{kind}{slot:04x}"
        self.tree = merkle_tree_from_string(tree)
        self.tree_string = tree
        self.base_millis = base_millis
        self.msgs = msgs
        self.pool = pool
        self.round = 0

    def timestamps(self, rnd: int) -> list:
        first = self.base_millis + rnd * max(self.msgs, 1)
        return [timestamp_to_string(Timestamp(first + j, 0, self.node))
                for j in range(self.msgs)]

    def next_request(self) -> protocol.SyncRequest:
        """Advance one round: the request carries the tree AFTER the
        client's own apply, which is also what the relay must answer."""
        stamps = self.timestamps(self.round)
        at = (self.slot * 131 + self.round * self.msgs) % max(len(self.pool), 1)
        messages = tuple(
            protocol.EncryptedCrdtMessage(s, self.pool[(at + j) % len(self.pool)])
            for j, s in enumerate(stamps))
        if stamps:
            deltas, _ = minute_deltas_host(stamps)
            self.tree = apply_prefix_xors(self.tree, deltas)
            self.tree_string = merkle_tree_to_string(self.tree)
        self.round += 1
        return protocol.SyncRequest(messages, self.owner, self.node, self.tree_string)

    def next_body(self) -> bytes:
        return protocol.encode_sync_request(self.next_request())

    def answered(self, body: bytes) -> bool:
        """The round's check, by the client: nothing to fetch, and the
        relay's tree equals the client's own fold."""
        resp = protocol.decode_sync_response(body)
        return resp.messages == () and resp.merkle_tree == self.tree_string
