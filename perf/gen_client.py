"""Seeded generators of the client cells: one owner's history and the
relay's answers to a device that restores it.

`build_messages` is `benchmarks/config2_single_chip.py::build_messages`
with its sizes as arguments; `build_responses` is the split and
encryption of `chip_smoke.client_phase`, with the relay's tree folded by
the plain reference's own code. Both are set-up: built once a
run, untimed. Module level imports nothing of JAX.
"""

import random

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from perf import load_module
from perf.gen import BASE_MILLIS, MNEMONIC  # noqa: F401 - the owner's mnemonic

# The example app's schema (examples/nextjs/pages/index.tsx) plus the
# note table benchmarks/config2 and chip_smoke.py add.
TABLES = (
    ("todo", ("title", "isCompleted", "categoryId")),
    ("todoCategory", ("name",)),
    ("todoNote", ("text",)),
)


def build_messages(n: int, seed: int, rows: int, nodes: int) -> list:
    """`n` cell writes of one owner in HLC order: message i has millis
    BASE + i // 4 and counter i % 4, a table, one of `rows` rows, one of
    the table's columns and one of `nodes` writer nodes by `seed`, and
    the value `v<i>`, so every cell (<= 5 x rows of them) is written
    n / (5 x rows) times on average and the last write wins."""
    rng = random.Random(seed)
    writers = [f"{rng.getrandbits(64):016x}" for _ in range(nodes)]
    out = []
    for i in range(n):
        table, cols = rng.choice(TABLES)
        out.append(CrdtMessage(
            timestamp_to_string(Timestamp(BASE_MILLIS + i // 4, i % 4, rng.choice(writers))),
            table, f"row{rng.randrange(rows)}", rng.choice(cols), f"v{i}"))
    return out


def split_responses(messages, responses: int) -> list:
    """The history cut into `responses` runs of equal length, in order."""
    per = -(-len(messages) // responses)
    return [messages[i:i + per] for i in range(0, len(messages), per)]


def build_responses(messages, responses: int, mnemonic: str) -> list:
    """The history as `responses` SyncResponses in order → the wire
    bytes of each: its messages as real OpenPGP ciphertext under the
    mnemonic's key, and the relay's tree after it: the plain reference's
    own fold (`perf/reference/client_todo.py`, not `core.merkle`) of
    every distinct timestamp so far. A client that has merged response k
    holds exactly that tree, so its sync ends."""
    from evolu_tpu.sync import protocol
    from evolu_tpu.sync.client import encrypt_messages

    reference = load_module("reference", "client_todo")
    wires, tree, seen = [], {}, set()
    for batch in split_responses(messages, responses):
        new = list(dict.fromkeys(m.timestamp for m in batch if m.timestamp not in seen))
        seen.update(new)  # the relay stores a timestamp once (INSERT OR IGNORE)
        parsed = [reference.parse_timestamp(t) for t in new]
        for (millis, _counter, _node), h in zip(parsed, reference.timestamp_hashes(parsed)):
            reference.tree_insert(tree, millis, h)
        wires.append(protocol.encode_sync_response(protocol.SyncResponse(
            tuple(encrypt_messages(batch, mnemonic)), reference.tree_to_string(tree))))
    return wires
