"""`client-todo-months` against its plain reference at a small size
(ISSUE 33): the history of `perf/gen_history.py` (sessions over days,
thousands of Merkle minutes) through `DbWorker(Config(backend="tpu"))`
as the benchmark's driver hands it over, beside
`perf/reference/client_todo.py` on the same messages. The full dump must
be equal, and after EACH `Receive` the tree string in `__clock` must be
the reference's, which is also the relay's in that response: the client
never asks to sync again. The one-minute shape is `client-todo`'s own
generator: the same code, a tree of one leaf.
"""

import itertools

import pytest

from evolu_tpu.core.types import TableDefinition
from evolu_tpu.obs import metrics
from evolu_tpu.runtime import messages as rmsg
from evolu_tpu.runtime.worker import DbWorker
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto
from evolu_tpu.utils.config import Config
from perf import gen_client, gen_history, load_module

reference = load_module("reference", "client_todo")
driver = load_module("drivers", "client_history")

needs_native = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed receive needs both native libraries")

MESSAGES, ROWS, NODES, RESPONSES = 4000, 50, 8, 4
DAYS, SESSIONS, SESSION_MINUTES = 30, 3, 30
NOW, STEP = 1_731_622_400_000, 1000  # the configuration's: after the history's end


def _config(config: str) -> dict:
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", f"{config}.json")) as f:
        return json.load(f)


def _history(shape: str, seed: int) -> list:
    if shape == "one-minute":
        return gen_client.build_messages(MESSAGES, seed, ROWS, NODES)
    return gen_history.build_messages(MESSAGES, seed, ROWS, NODES,
                                      DAYS, SESSIONS, SESSION_MINUTES)


@needs_native
@pytest.mark.parametrize("shape", ["one-minute", "months"])
@pytest.mark.parametrize("seed", [5, 2**31 + 33])
def test_worker_equals_the_reference_after_each_receive(seed, shape):
    messages = _history(shape, seed)
    wires = gen_history.build_responses(messages, RESPONSES, gen_history.MNEMONIC)
    minutes0 = metrics.get_counter("evolu_merkle_fold_minutes_total")
    outputs, syncs = [], []
    db = native.open_database(backend="native")
    worker = DbWorker(db, Config(backend="tpu"), on_output=outputs.append,
                      post_sync=syncs.append, now=itertools.count(NOW, STEP).__next__)
    worker.start(gen_history.MNEMONIC)
    twin = reference.ReferenceClient(gen_history.TABLES, gen_history.MNEMONIC)
    try:
        worker.post(rmsg.UpdateDbSchema(tuple(
            TableDefinition.of(t, cols) for t, cols in gen_history.TABLES)))
        now = itertools.count(NOW, STEP)
        batches = gen_history.split_responses(messages, RESPONSES)
        for wire, batch in zip(wires, batches):
            packed, relay_tree = native_crypto.decrypt_response_columns(
                wire, gen_history.MNEMONIC)
            worker.post(rmsg.Receive(packed, relay_tree, None))
            worker.flush()
            twin.receive([(m.timestamp, m.table, m.row, m.column, m.value)
                          for m in batch], next(now))
            stored = db.exec_sql_query('SELECT "merkleTree" FROM "__clock"')[0]["merkleTree"]
            assert stored == reference.tree_to_string(twin.tree) == relay_tree
        got, want = driver.client.dump(db), twin.dump()
    finally:
        worker.stop()
        db.close()
        twin.close()
    assert not [o.error for o in outputs if isinstance(o, rmsg.OnError)]
    assert syncs == []  # no diff after any Receive: no sync request pushed
    assert sorted(got) == sorted(want)
    for key in want:
        assert got[key] == want[key], key
    assert len(got["__message"]) == MESSAGES
    # A fold a Receive, and as many minutes as the responses hold (a
    # minute that straddles two responses is folded in both).
    distinct = sum(len({m.timestamp[:16] for m in b}) for b in batches)
    assert metrics.get_counter("evolu_merkle_fold_minutes_total") - minutes0 == distinct
    assert distinct == RESPONSES if shape == "one-minute" else distinct > 2000


@pytest.mark.parametrize("seed", [5, 2**31 + 33])
def test_generator_same_seed_same_bytes_and_strictly_increasing(seed):
    args = (MESSAGES, seed, ROWS, NODES, DAYS, SESSIONS, SESSION_MINUTES)
    a, b = gen_history.build_messages(*args), gen_history.build_messages(*args)
    assert a == b and a != gen_history.build_messages(MESSAGES, seed + 1, *args[2:])
    stamps = [m.timestamp for m in a]
    assert all(len(t) == 46 for t in stamps)
    assert all(x < y for x, y in zip(stamps, stamps[1:]))  # HLC order, none twice
    assert len({t[:16] for t in stamps}) == DAYS * SESSIONS * SESSION_MINUTES
    # Everything but the time is gen_client's draw, message for message.
    plain = gen_client.build_messages(MESSAGES, seed, ROWS, NODES)
    assert [(m.table, m.row, m.column, m.value, m.timestamp[30:]) for m in a] == \
        [(m.table, m.row, m.column, m.value, m.timestamp[30:]) for m in plain]


def test_generator_minutes_at_full_size_without_building_messages():
    cfg = _config("client-todo-months")
    want = cfg["days"] * cfg["sessions_per_day"] * cfg["session_minutes"]
    minutes = gen_history.active_minutes(
        7, cfg["days"], cfg["sessions_per_day"], cfg["session_minutes"])
    assert minutes == sorted(set(minutes)) and len(minutes) == want == 32_850
    dealt = gen_history.message_minutes(cfg["messages"], minutes)
    assert dealt == sorted(dealt)
    assert abs(len(set(dealt)) - want) <= 0.05 * want
    # The pinned wall clock lies after the last message and within the
    # year after the first: the HLC fold's drift check holds.
    assert minutes[-1] * 60_000 + 60_000 <= cfg["now_millis"]
    per_response = cfg["messages"] // cfg["responses"]
    assert 8_000 <= len(set(dealt[:per_response])) <= 8_400
