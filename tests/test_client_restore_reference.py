"""`client-todo` against its plain reference at a small size (ISSUE 29).

`DbWorker(Config(backend="tpu"))` is handed the relay's responses as
wire bytes, as the benchmark's driver hands them (`perf/drivers/client.py`:
`decrypt_response_columns` → `Receive(PackedReceive)` → `flush`), and
`perf/reference/client_todo.py` applies the same messages one at a time
over stdlib sqlite3. The full dump must be equal: `__message`, the clock
as millis + counter and tree, `__owner` and the three tables. The cell's
`correct` rests on the same comparison at full size on the chip.
"""

import itertools
import random

import pytest

from evolu_tpu.core.types import TableDefinition
from evolu_tpu.obs import metrics
from evolu_tpu.runtime import messages as rmsg
from evolu_tpu.runtime.worker import DbWorker
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto
from evolu_tpu.utils.config import Config
from perf import gen_client, load_module

reference = load_module("reference", "client_todo")
driver = load_module("drivers", "client")

pytestmark = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed receive needs both native libraries")

NOW, STEP = 1_700_010_000_000, 1000
MESSAGES, ROWS, NODES = 1200, 20, 4


def _history(seed: int, duplicates: bool) -> list:
    """A seeded history; with `duplicates`, a tenth of it is delivered
    twice: some right behind the original, some far behind it, so that a
    response repeats its own messages and earlier responses'."""
    messages = gen_client.build_messages(MESSAGES, seed, ROWS, NODES)
    if not duplicates:
        return messages
    rng = random.Random(seed + 1)
    out = []
    for m in messages:
        out.append(m)
        if rng.random() < 0.05:
            out.append(m)
        if rng.random() < 0.05:
            out.append(rng.choice(out))
    return out


def _restore(worker, wires, outputs) -> None:
    for wire in wires:
        packed, tree = native_crypto.decrypt_response_columns(wire, gen_client.MNEMONIC)
        worker.post(rmsg.Receive(packed, tree, None))
        worker.flush()
    errors = [o.error for o in outputs if isinstance(o, rmsg.OnError)]
    assert not errors, errors


@pytest.mark.parametrize("variant", ["in-batch-duplicates", "second-restore"])
@pytest.mark.parametrize("responses", [1, 4])
@pytest.mark.parametrize("seed", [3, 11, 2**31 + 5])
def test_worker_end_state_equals_the_plain_reference(seed, responses, variant):
    messages = _history(seed, duplicates=variant == "in-batch-duplicates")
    wires = gen_client.build_responses(messages, responses, gen_client.MNEMONIC)
    rounds = 2 if variant == "second-restore" else 1

    packed0 = metrics.get_counter("evolu_apply_batches_total", route="packed")
    outputs, syncs = [], []
    db = native.open_database(backend="native")
    worker = DbWorker(db, Config(backend="tpu"), on_output=outputs.append,
                      post_sync=syncs.append, now=itertools.count(NOW, STEP).__next__)
    worker.start(gen_client.MNEMONIC)
    twin = reference.ReferenceClient(gen_client.TABLES, gen_client.MNEMONIC)
    try:
        worker.post(rmsg.UpdateDbSchema(tuple(
            TableDefinition.of(t, cols) for t, cols in gen_client.TABLES)))
        now = itertools.count(NOW, STEP)
        for k in range(rounds):  # the second restore finds the first's rows
            _restore(worker, wires, outputs)
            if k == 0 and variant == "second-restore":
                # The relay's tree in each response is the reference's own
                # fold: the client's equals it, so it never asks to sync again.
                assert syncs == []
            for batch in gen_client.split_responses(messages, responses):
                twin.receive([(m.timestamp, m.table, m.row, m.column, m.value)
                              for m in batch], next(now))
        got = driver.dump(db)
        want = twin.dump()
    finally:
        worker.stop()
        db.close()
        twin.close()
    assert sorted(got) == sorted(want) == sorted(
        ["__message", "__clock", "__owner", *(t for t, _c in gen_client.TABLES)])
    for key in want:
        assert got[key] == want[key], key
    # Stored once whatever was delivered twice, and through the packed route.
    assert len(got["__message"]) == MESSAGES
    assert metrics.get_counter("evolu_apply_batches_total", route="packed") - packed0 \
        == rounds * responses


# The reference folds the compared tree and clock with code of its own,
# held here to the upstream project's own snapshots
# (packages/evolu/test/__snapshots__/merkleTree.test.ts.snap, timestamp.test.ts),
# not to `evolu_tpu.core`: a fault there must not pass both sides.
NODE1 = "0000000000000001"


def _tree_of(*millis: int) -> dict:
    tree = {}
    stamps = [(m, 0, NODE1) for m in millis]
    for (m, _c, _n), h in zip(stamps, reference.timestamp_hashes(stamps)):
        reference.tree_insert(tree, m, h)
    return tree


def test_reference_tree_matches_upstream_snapshots():
    assert _tree_of(0) == {"hash": -1416139081, "0": {"hash": -1416139081}}
    node = tree = _tree_of(1656873738591)
    for digit in "1220221222001120":
        assert node["hash"] == -468843282
        node = node[digit]
    assert node == {"hash": -468843282}
    both = _tree_of(1656873738591, 0)
    assert both == _tree_of(0, 1656873738591) and both["hash"] == 1335454297
    text = reference.tree_to_string(both)
    assert text.startswith('{"0":{"hash":-1416139081},"1":{"2":') and " " not in text
    assert text.endswith('"hash":1335454297}')
    assert reference.tree_to_string({}) == "{}" and tree["hash"] == -468843282


def test_reference_timestamp_string_form():
    text = "2022-07-03T18:42:18.591Z-00AF-0123456789abcdef"
    assert reference.parse_timestamp(text) == (1656873738591, 0xAF, "0123456789abcdef")
    assert reference.render_timestamp(1656873738591, 0xAF, "0123456789abcdef") == text
    assert reference.render_timestamp(0, 0, "0" * 16) == \
        "1970-01-01T00:00:00.000Z-0000-0000000000000000"
    assert reference.timestamp_hashes([(0, 0, "0" * 16)]) == [4179357717]


@pytest.mark.parametrize("local, remote, now, want", [
    # timestamp.ts:125-165, the four counter rules and the wall clock
    ((5, 2, "a"), (5, 7, "b"), 1, (5, 8, "a")),   # equal millis: larger counter + 1
    ((5, 2, "a"), (3, 7, "b"), 1, (5, 3, "a")),   # local ahead: its counter + 1
    ((3, 2, "a"), (5, 7, "b"), 1, (5, 8, "a")),   # remote ahead: its counter + 1
    ((3, 2, "a"), (5, 7, "b"), 9, (9, 0, "a")),   # the wall clock ahead: counter 0
])
def test_reference_clock_rule(local, remote, now, want):
    assert reference.receive_clock(local, remote, now) == want


@pytest.mark.parametrize("local, remote, now", [
    ((0, 0, "a"), (60_002, 0, "b"), 1),           # more than a minute ahead of now
    ((5, 0, "a"), (5, 0, "a"), 5),                # the device's own node
    ((5, 65_535, "a"), (3, 0, "b"), 1),           # the counter is full
])
def test_reference_clock_refuses(local, remote, now):
    with pytest.raises(ValueError):
        reference.receive_clock(local, remote, now)
