"""`client-todo-months` against its plain reference at a small size
(ISSUE 33): the history of `perf/gen_history.py` (sessions over days,
thousands of Merkle minutes) through `DbWorker(Config(backend="tpu"))`
as the benchmark's driver hands it over, beside
`perf/reference/client_todo.py` on the same messages. The full dump must
be equal, and after EACH `Receive` the tree string in `__clock` must be
the reference's, which is also the relay's in that response: the client
never asks to sync again. The one-minute shape is `client-todo`'s own
generator: the same code, a tree of one leaf.

The worker compares texts before it parses. Held here, through a
`DbWorker`: a relay tree that differs in one minute still takes the
parse and the walk; after a rollback, a reset or a restore of the owner
the next fold starts from what `__clock` holds; a `Send` pushes the text
`update_clock` wrote. The tree is carried as its text (`MerkleText`)
and every fold goes text to text
(`evolu_merkle_fold_route_total{route=text}`), over a year's history
too, and on a `Send`; nothing is dumped.
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
YEAR = 365  # days: the configuration's history, at this test's message count
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
                                      YEAR if shape == "year" else DAYS,
                                      SESSIONS, SESSION_MINUTES)


@needs_native
@pytest.mark.parametrize("shape", ["one-minute", "months", "year"])
@pytest.mark.parametrize("seed", [5, 2**31 + 33])
def test_worker_equals_the_reference_after_each_receive(seed, shape):
    messages = _history(shape, seed)
    wires = gen_history.build_responses(messages, RESPONSES, gen_history.MNEMONIC)
    minutes0 = metrics.get_counter("evolu_merkle_fold_minutes_total")
    routes0 = _routes()
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
    # Every fold went text to text: one a Receive, none by the dict route.
    assert _routes() == {"text": routes0["text"] + RESPONSES, "dict": routes0["dict"]}


def _routes():
    return {route: metrics.get_counter("evolu_merkle_fold_route_total", route=route)
            for route in ("text", "dict")}


class _Restoring:
    """A worker on the months (or a year's) history beside its reference twin."""

    def __init__(self, seed: int = 5, shape: str = "months"):
        self.messages = _history(shape, seed)
        self.batches = gen_history.split_responses(self.messages, RESPONSES)
        self.wires = gen_history.build_responses(self.messages, RESPONSES, gen_history.MNEMONIC)
        self.outputs, self.syncs = [], []
        self.db = native.open_database(backend="native")
        self.worker = DbWorker(self.db, Config(backend="tpu"), on_output=self.outputs.append,
                               post_sync=self.syncs.append,
                               now=itertools.count(NOW, STEP).__next__)
        self.worker.start(gen_history.MNEMONIC)
        self.twin = reference.ReferenceClient(gen_history.TABLES, gen_history.MNEMONIC)
        self._now = itertools.count(NOW, STEP)
        self.schema()

    def schema(self):
        self.worker.post(rmsg.UpdateDbSchema(tuple(
            TableDefinition.of(t, cols) for t, cols in gen_history.TABLES)))
        self.worker.flush()

    def relay_tree(self, k: int) -> str:
        """The twin takes batch k; → the relay's tree after it."""
        self.twin.receive([(m.timestamp, m.table, m.row, m.column, m.value)
                           for m in self.batches[k]], next(self._now))
        return reference.tree_to_string(self.twin.tree)

    def receive(self, k: int, tree: str, previous_diff=None):
        packed, _tree = native_crypto.decrypt_response_columns(
            self.wires[k], gen_history.MNEMONIC)
        self.worker.post(rmsg.Receive(packed, tree, previous_diff))
        self.worker.flush()

    def stored(self) -> str:
        return self.db.exec_sql_query('SELECT "merkleTree" FROM "__clock"')[0]["merkleTree"]

    def errors(self):
        return [o.error for o in self.outputs if isinstance(o, rmsg.OnError)]

    def close(self):
        self.worker.stop()
        self.db.close()
        self.twin.close()


def _text_counts():
    return {(kind, leg): metrics.get_counter(f"evolu_merkle_tree_text_{kind}_total", leg=leg)
            for kind in ("checks", "hits") for leg in ("load", "remote")}


def _moved(before):
    return {k: v - before[k] for k, v in _text_counts().items() if v != before[k]}


@pytest.fixture
def restoring():
    r = _Restoring()
    yield r
    r.close()


def _one_minute_ahead(tree_text: str):
    """→ (a relay tree that holds one timestamp more, that minute's millis)."""
    from evolu_tpu.core.merkle import (
        insert_into_merkle_tree, merkle_tree_from_string, merkle_tree_to_string)
    from evolu_tpu.core.timestamp import timestamp_from_string

    extra = timestamp_from_string("2024-06-01T12:00:30.000Z-0000-00000000000000aa")
    ahead = insert_into_merkle_tree(extra, merkle_tree_from_string(tree_text))
    return merkle_tree_to_string(ahead), extra.millis // 60_000 * 60_000


@needs_native
def test_equal_texts_spare_both_parses_and_a_differing_minute_takes_the_walk(restoring):
    r = restoring
    before = _text_counts()
    r.receive(0, r.relay_tree(0))
    assert r.stored() == reference.tree_to_string(r.twin.tree) and r.syncs == []
    # The relay's text was the client's own; the load compared nothing.
    assert _moved(before) == {("checks", "remote"): 1, ("hits", "remote"): 1}
    # The relay is one minute ahead: the comparison misses, the tree is
    # parsed and walked, and the request carries that minute and the
    # client's own text.
    before = _text_counts()
    tree1 = r.relay_tree(1)
    ahead, minute = _one_minute_ahead(tree1)
    r.receive(1, ahead)
    assert r.errors() == [] and r.stored() == tree1
    assert _moved(before) == {("checks", "remote"): 1}
    (request,) = r.syncs
    assert request.previous_diff == minute and request.merkle_tree == tree1
    assert [m.timestamp for m in request.messages] == sorted(
        m.timestamp for b in r.batches[:2] for m in b
        if m.timestamp > "2024-06-01T12:00:00.000Z")


@needs_native
def test_a_rolled_back_receive_leaves_no_stale_tree(restoring):
    r = restoring
    tree0 = r.relay_tree(0)
    r.receive(0, tree0)
    tree1 = r.relay_tree(1)
    ahead, minute = _one_minute_ahead(tree1)
    # The same diff twice: SyncError AFTER update_clock wrote the new
    # tree, so the whole Receive rolls back.
    r.receive(1, ahead, previous_diff=minute)
    assert [type(e).__name__ for e in r.errors()] == ["SyncError"]
    assert r.stored() == tree0
    before = _text_counts()
    r.receive(1, tree1)
    # The fold started from `__clock` as it was rolled back to; folding
    # batch 1 into the tree the rolled-back Receive made would XOR it in
    # twice.
    assert _moved(before) == {("checks", "remote"): 1, ("hits", "remote"): 1}
    assert r.stored() == tree1 and r.syncs == []
    assert len(r.errors()) == 1


@needs_native
@pytest.mark.parametrize("wipe", [("RestoreOwner",), ("ResetOwner", "RestoreOwner")])
def test_a_reset_or_restored_owner_starts_from_its_own_clock(restoring, wipe):
    r = restoring
    r.receive(0, r.relay_tree(0))
    r.receive(1, r.relay_tree(1))
    for command in wipe:
        r.worker.post(rmsg.ResetOwner() if command == "ResetOwner"
                      else rmsg.RestoreOwner(gen_history.MNEMONIC))
        r.worker.flush()
    r.schema()
    assert r.stored() == "{}"
    fresh = reference.ReferenceClient(gen_history.TABLES, gen_history.MNEMONIC)
    try:
        fresh.receive([(m.timestamp, m.table, m.row, m.column, m.value)
                       for m in r.batches[0]], NOW)
        tree0 = reference.tree_to_string(fresh.tree)
    finally:
        fresh.close()
    before = _text_counts()
    r.receive(0, tree0)
    assert r.errors() == [] and r.stored() == tree0 and r.syncs == []
    assert _moved(before) == {("checks", "remote"): 1, ("hits", "remote"): 1}


@pytest.fixture
def restored_year():
    r = _Restoring(shape="year")
    for k in range(RESPONSES):
        r.receive(k, r.relay_tree(k))
    yield r
    r.close()


@needs_native
def test_a_send_pushes_the_text_update_clock_wrote(restored_year, monkeypatch):
    import json

    from evolu_tpu.core.merkle import (
        insert_into_merkle_tree, merkle_tree_from_string, merkle_tree_to_string)
    from evolu_tpu.core.timestamp import timestamp_from_string

    r = restored_year  # a year's tree: thousands of minutes
    tree0 = r.stored()
    assert len(tree0) > 100_000 and r.errors() == [] and r.syncs == []
    calls = []
    for name in ("dumps", "loads"):
        real = getattr(json, name)
        monkeypatch.setattr(json, name, lambda *a, real=real, name=name, **k:
                            calls.append(name) or real(*a, **k))
    routes0 = _routes()
    r.worker.post(rmsg.Send((rmsg.NewCrdtMessage("todo", "row1", "title", "mine"),)))
    r.worker.flush()
    assert r.errors() == []
    (request,) = r.syncs
    assert request.merkle_tree == r.stored() and len(request.messages) == 1
    assert calls == []  # the mutate folded text to text: no parse, no dump
    assert _routes() == {"text": routes0["text"] + 1, "dict": routes0["dict"]}
    monkeypatch.undo()
    want = insert_into_merkle_tree(timestamp_from_string(request.messages[0].timestamp),
                                   merkle_tree_from_string(tree0))
    assert r.stored() == merkle_tree_to_string(want)
    # A Sync of the unchanged clock sends `__clock`'s text as it is.
    before = _text_counts()
    r.worker.post(rmsg.Sync())
    r.worker.flush()
    assert r.syncs[-1].merkle_tree == r.stored() and r.syncs[-1].messages == ()
    assert _moved(before) == {}


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
