"""Seeded generators of a mixed cell: skewed owners, pulls and one-field
updates, several devices an owner (`relay-skewed.ycsb-a`, ISSUE 40).

YCSB core workload A read as sync rounds: a key is an OWNER, drawn by
rank with P(r) proportional to r^-0.99 (`ZipfianGenerator`'s constant)
by the exact inverse CDF, the ranks scrambled onto the owners by a
permutation from the seed; a read is a round that carries no message
(a pull); an update is a round that carries one column's change as
Evolu emits it, two messages. Whoever sends a round is a `Device`: one
of an owner's devices, with its own node id, its own tree and the set
of timestamps it holds. Everything a device sends follows from (node,
update count), so the parent regenerates every acknowledged row.

Module level imports nothing of JAX (`perf/loadgen_mix.py` runs this in
child processes that must never touch the chip) and nothing of numpy.
"""

import bisect
import itertools
import os
import random
import threading
from array import array

from evolu_tpu.core.merkle import (
    apply_prefix_xors, merkle_tree_from_string, merkle_tree_to_string,
    minute_deltas_host)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.sync import protocol

STAMP = 46  # characters of a canonical timestamp


class OwnerDraw:
    """Ranks 0..owners-1 with P(rank r) proportional to (r + 1)^-theta,
    and the permutation (from `seed`) that scrambles them onto owners."""

    def __init__(self, owners: int, seed: int, theta: float):
        self.cdf = list(itertools.accumulate(r ** -theta for r in range(1, owners + 1)))
        self.owner_of_rank = random.Random(seed).sample(range(owners), owners)

    def rank(self, rng: random.Random) -> int:
        at = bisect.bisect_right(self.cdf, rng.random() * self.cdf[-1])
        return min(at, len(self.cdf) - 1)

    def share(self, ranks: int) -> float:
        """The probability of the `ranks` hottest ranks together."""
        return self.cdf[ranks - 1] / self.cdf[-1]


def thread_rng(seed: int, slot: int) -> random.Random:
    """A connection's own stream of draws: `Random(seed, slot)`."""
    return random.Random(f"{seed}:{slot}")


def draw_round(draw: OwnerDraw, rng: random.Random, update_share: float) -> tuple:
    """One round of the mix → (rank, is it an update); the operation is
    independent of the owner."""
    rank = draw.rank(rng)
    return rank, rng.random() < update_share


def write_owner_file(path: str, requests) -> None:
    """What a device needs of its owner at first use, for every owner of
    the preload: one record `owner \\n tree \\n timestamps` a request, and
    `path.idx` with the records' offsets, so that a child reads only the
    owners it draws."""
    offsets = array("q", [0])
    with open(path, "wb") as f:
        for r in requests:
            record = "\n".join(
                (r.user_id, r.merkle_tree, "".join(m.timestamp for m in r.messages)))
            offsets.append(offsets[-1] + f.write(record.encode("ascii")))
    with open(path + ".idx", "wb") as f:
        offsets.tofile(f)


class OwnerFile:
    """`write_owner_file`'s reader; `read` is safe from any thread."""

    def __init__(self, path: str):
        self.offsets = array("q")
        with open(path + ".idx", "rb") as f:
            self.offsets.frombytes(f.read())
        self._fd = os.open(path, os.O_RDONLY)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def read(self, index: int) -> tuple:
        """→ (owner, its preload tree, its preload timestamps)."""
        lo, hi = self.offsets[index], self.offsets[index + 1]
        owner, tree, stamps = os.pread(self._fd, hi - lo, lo).decode("ascii").split("\n")
        return owner, tree, [stamps[i:i + STAMP] for i in range(0, len(stamps), STAMP)]

    def close(self) -> None:
        os.close(self._fd)


def device_node(kind: str, process: int, owner_index: int) -> str:
    """The node id of the device of `owner_index` in `process`: 16 hex
    characters that no preloaded row ends in (those start with 0)."""
    return f"{kind}{process:03x}{owner_index:012x}"


def update_messages(node: str, count: int, base_millis: int, msgs: int, pool) -> tuple:
    """The `count`-th update of the device `node`: `msgs` new messages
    (the column and `updatedAt`) on its own node id, a minute past every
    preloaded row, with pool ciphertexts."""
    at = (int(node[4:], 16) * 131 + int(node[1:4], 16) * 17 + count * msgs) % len(pool)
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(Timestamp(base_millis + count * msgs + j, 0, node)),
            pool[(at + j) % len(pool)])
        for j in range(msgs))


class Device:
    """One device of one owner: what it holds (`held`, its tree) and the
    rounds it sends, one at a time (`lock`). A round is `request`, the
    HTTP exchange, `merge`."""

    def __init__(self, owner: str, node: str, tree: str, held, base_millis: int,
                 msgs: int, pool):
        self.owner = owner
        self.node = node
        self.tree = merkle_tree_from_string(tree)
        self.tree_string = tree
        self.held = set(held)
        self.base_millis = base_millis
        self.msgs = msgs
        self.pool = pool
        self.updates = 0
        self.failed = False
        self.lock = threading.Lock()

    def _fold(self, stamps) -> None:
        deltas, _ = minute_deltas_host(stamps)
        self.tree = apply_prefix_xors(self.tree, deltas)
        self.tree_string = merkle_tree_to_string(self.tree)
        self.held.update(stamps)

    def request(self, update: bool) -> protocol.SyncRequest:
        """A pull carries no message and the tree as it stands; an
        update carries its new messages and the tree AFTER the device's
        own apply."""
        messages = ()
        if update:
            messages = update_messages(self.node, self.updates, self.base_millis,
                                       self.msgs, self.pool)
            self._fold([m.timestamp for m in messages])
            self.updates += 1
        return protocol.SyncRequest(messages, self.owner, self.node, self.tree_string)

    def merge(self, response: protocol.SyncResponse) -> bool:
        """Fold the rows of an answer that the device did not hold →
        guarantee (d): is its tree now byte-equal to the answer's?"""
        new = [m.timestamp for m in response.messages if m.timestamp not in self.held]
        if new:
            self._fold(new)
        return self.tree_string == response.merkle_tree


def round_record(device: Device, owner_index: int, update: bool, t_send: float,
                 t_done: float, answer, error=None) -> dict:
    """One round of the log the driver's check reads, made after the
    device's merge: who sent what, the two instants on the host's
    monotonic clock, and what the device then held."""
    return {"owner": owner_index, "node": device.node, "update": update,
            "count": device.updates - 1 if update else None,
            "t_send": t_send, "t_done": t_done, "ok": error is None, "error": error,
            "held": len(device.held),
            "answer": len(answer.messages) if answer is not None else 0}
