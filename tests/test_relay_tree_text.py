"""The relay's Merkle trees fold as their text.

A relay pass folds every owner's minute deltas into its stored tree
TEXT in one native call (`core.merkle.fold_texts` →
`eh_fold_tree_texts`, `engine._fold_trees`), and the per-request trees
of an owner with several requests in one more (`engine._tree_prefixes`);
the device's compacted outputs reach it as numpy runs
(`engine.PassDeltas`), never as a dict a segment. What is pinned: each
text byte for byte what `merkle_tree_to_string(apply_prefix_xors(parsed,
deltas))` writes, the dict route for exactly the owners the splice
refuses; whole passes against `RelayStore(":memory:", "python")`
replaying the same requests one at a time; the respond's equal-text
rule against the parse-and-diff route; one native call a fold.
"""

import json
import random
import zlib

import numpy as np
import pytest

from conftest import relay_store_dump
from evolu_tpu.core import merkle as merkle_mod
from evolu_tpu.core.merkle import (
    MerkleText,
    apply_prefix_xors,
    fold_texts,
    merkle_tree_from_string,
    merkle_tree_to_string,
    minutes_base3,
)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import ledger as ledger_mod
from evolu_tpu.obs import metrics
from evolu_tpu.server import engine as engine_mod
from evolu_tpu.server.engine import BatchReconciler, PassDeltas
from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
from evolu_tpu.storage import native
from evolu_tpu.sync import protocol

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="the text route needs the native library")

MINUTE = 60_000
LO, HI = 3 ** 15, 3 ** 16  # the minutes with a 16-digit key
BASE = 1_700_000_000_000
Y2023 = BASE // MINUTE


def _text_of(keys: dict) -> str:
    return merkle_tree_to_string(apply_prefix_xors({}, keys))


def _dict_route(text: str, minutes, deltas) -> str:
    keys = {minutes_base3(m * MINUTE): d for m, d in zip(minutes, deltas)}
    return merkle_tree_to_string(apply_prefix_xors(merkle_tree_from_string(text), keys))


def _owners(seed: int):
    """Randomized owners: (stored text, sorted minutes, int32 deltas),
    1-300 minutes each over a few days, some deltas 0, some owners with
    the initial empty tree; the last two are refused by the splice: a
    text with whitespace, a minute without a 16-digit key."""
    rng = random.Random(seed)
    out = []
    for j in range(40):
        start = rng.randrange(Y2023 - 30 * 1440, Y2023)
        stored = {} if j % 5 == 0 else {
            minutes_base3((start + rng.randrange(5 * 1440)) * MINUTE):
                rng.randrange(-2 ** 31, 2 ** 31) for _ in range(rng.randint(1, 60))}
        minutes = sorted(rng.sample(range(start, start + 5 * 1440), rng.randint(1, 300)))
        deltas = [rng.choice([0, -2 ** 31, rng.randrange(-2 ** 31, 2 ** 31)]) for _ in minutes]
        out.append([_text_of(stored), minutes, deltas])
    out[-2][0] = json.dumps(json.loads(out[-2][0]))  # ", " and ": "
    out[-1][1] = [LO - 1] + out[-1][1]  # 1997-04-12: a 15-digit key
    out[-1][2] = [7] + out[-1][2]
    return out


def _runs(owners):
    starts = np.cumsum([0] + [len(m) for _t, m, _d in owners])
    minutes = np.array([m for _t, ms, _d in owners for m in ms], np.int64)
    deltas = np.array([d for _t, _m, ds in owners for d in ds], np.int32)
    return starts, minutes, deltas


@pytest.mark.parametrize("seed", range(6))
def test_the_batched_fold_is_byte_identical_to_the_dict_route(seed):
    """`fold_texts` against `merkle_tree_to_string(apply_prefix_xors(
    parsed, deltas))` owner by owner, and None for exactly the owners
    the splice refuses."""
    owners = _owners(seed)
    got = fold_texts([t for t, _m, _d in owners], *_runs(owners))
    assert len(got) == len(owners)
    for j, (text, minutes, deltas) in enumerate(owners):
        if j >= len(owners) - 2:
            assert got[j] is None
        else:
            assert got[j] == _dict_route(text, minutes, deltas)


@pytest.mark.parametrize("seed", range(3))
def test_fold_trees_takes_the_dict_route_for_the_refused_owners_alone(seed):
    """Through `_fold_trees`: every owner's row is the dict route's text,
    in pass order; a `MerkleText` for the spliced owners, a dict for the
    two refused ones; the counter's items say so."""
    owners = _owners(seed)
    names = [f"u{j}" for j in range(len(owners))]
    starts, minutes, deltas = _runs(owners)
    by_owner = PassDeltas(names, {}, names, (np.arange(len(names)), starts, minutes, deltas))
    stored = {u: t for u, (t, _m, _d) in zip(names, owners)}
    trees, strings, tree_rows = {}, {}, [[], []]
    items = engine_mod._fold_trees(
        by_owner, stored, tree_rows, lambda u: int(u[1:]) % 2, trees, strings)
    assert {labels["route"]: n for _name, n, labels in items} == {
        "text": len(owners) - 2, "dict": 2}
    for u, (text, ms, ds) in zip(names, owners):
        assert strings[u] == _dict_route(text, ms, ds)
        assert merkle_tree_to_string(trees[u]) == strings[u]
        assert isinstance(trees[u], MerkleText) == (u not in names[-2:])
    assert [u for rows in tree_rows for u, _t in rows] == (
        names[0::2] + names[1::2])
    assert all(t == strings[u] for rows in tree_rows for u, t in rows)


@pytest.mark.parametrize("text, minutes", [
    ('{"hash":1,"0":{"hash":1}}', [Y2023]),  # "hash" before a child
    ('{"-":{"hash":1},"hash":1}', [Y2023]),  # a negative minute's key
    ('{"hash":2147483648}', [Y2023]),  # over int32
    ("", [Y2023]),  # no text at all
    ("{}", [HI]),  # from 2051-11-04: a 17-digit key
    ("{}", [Y2023 + 1, Y2023]),  # out of order
    ('{"hash": 1}', []),  # no minute, and not canonical
], ids=["hash-first", "dash-key", "over-int32", "empty", "post-2051", "unsorted", "no-minute"])
def test_a_refused_tree_leaves_its_neighbours_folded(text, minutes):
    """A tree the splice refuses is None in its place; the trees around
    it fold as `eh_fold_tree_text` folds each alone, and a tree with no
    minute comes back as the same str once it is known canonical."""
    good = _text_of({minutes_base3(Y2023 * MINUTE): 5})
    texts = [good, text, good, good]
    runs = [[Y2023 - 3, Y2023 + 9], minutes, [Y2023], []]
    starts = np.cumsum([0] + [len(r) for r in runs])
    flat = np.array([m for r in runs for m in r], np.int64)
    deltas = np.arange(1, len(flat) + 1, dtype=np.int32)
    got = fold_texts(texts, starts, flat, deltas)
    assert got[1] is None
    for j in (0, 2):
        single = merkle_mod._fold_text(good, flat[starts[j]:starts[j + 1]],
                                       deltas[starts[j]:starts[j + 1]])
        assert got[j] == single[0].text
    assert got[3] is good


def _stamp(owner: str, minute: int, i: int) -> str:
    node = f"{zlib.crc32(owner.encode()):016x}"
    return timestamp_to_string(Timestamp(BASE + minute * MINUTE + i * 17, i % 4, node))


def _request(owner, stamps, tree="{}"):
    msgs = tuple(protocol.EncryptedCrdtMessage(t, b"ct:" + t[-20:].encode()) for t in stamps)
    return protocol.SyncRequest(msgs, owner, "f" * 16, tree)


def _folds():
    return {r: metrics.get_counter("evolu_engine_tree_fold_total", route=r)
            for r in ("text", "dict")}


def _replay(store, oracle, eng, batches):
    """Each batch as one pass of `reconcile_wire`, against the same
    requests one at a time through the python relay; → the folds
    counted."""
    before = _folds()
    for reqs in batches:
        with ledger_mod.quarantine():
            want = [protocol.encode_sync_response(oracle.sync(r)) for r in reqs]
            assert eng.reconcile_wire(reqs) == want
    dump = relay_store_dump(store)
    assert sorted(r for msgs, _t in dump for r in msgs) == oracle.db.exec(
        'SELECT * FROM "message" ORDER BY "timestamp", "userId"')
    assert sorted(r for _m, ts in dump for r in ts) == oracle.db.exec(
        'SELECT * FROM "merkleTree" ORDER BY "userId"')
    return {r: n - before[r] for r, n in _folds().items()}


def test_a_pass_of_2000_owners_equals_the_python_relay():
    """2,000 owners of 1-4 rows over 1-3 minutes in one pass, then a
    second pass onto their stored trees: every answer and both tables
    as the sequential relay leaves them; every owner folded as text."""
    rng = random.Random(44)
    owners = [f"owner-{i}" for i in range(2000)]
    batches = [[_request(u, [_stamp(u, p * 10 + rng.randrange(3), i)
                             for i in range(rng.randint(1, 4))]) for u in owners]
               for p in range(2)]
    store = ShardedRelayStore(":memory:", "native", shards=8)
    oracle = RelayStore(":memory:", "python")
    eng = BatchReconciler(store)
    try:
        assert _replay(store, oracle, eng, batches) == {"text": 4000, "dict": 0}
    finally:
        eng.close(), store.close(), oracle.close()


def test_rows_already_stored_fold_their_new_rows_as_text(monkeypatch):
    """A pass that re-sends stored rows beside new ones: those owners'
    deltas are recomputed on the host from the new rows only
    (`_recompute_duplicate_owners`), fed to the same native pass as
    sorted minutes; an owner whose rows were all stored folds nothing."""
    owners = [f"dup-{i}" for i in range(60)]
    first = [_request(u, [_stamp(u, i % 3, i) for i in range(6)]) for u in owners]
    second = [_request(u, [_stamp(u, i % 3, i) for i in range(3, 6 if j % 4 == 0 else 9)])
              for j, u in enumerate(owners)]
    store = ShardedRelayStore(":memory:", "native", shards=4)
    oracle = RelayStore(":memory:", "python")
    eng = BatchReconciler(store)
    recomputed = []
    real = BatchReconciler._recompute_duplicate_owners

    def spy(self, st, was_new, deltas_by_owner):
        real(self, st, was_new, deltas_by_owner)
        recomputed.append(dict(deltas_by_owner.host))

    monkeypatch.setattr(BatchReconciler, "_recompute_duplicate_owners", spy)
    try:
        assert _replay(store, oracle, eng, [first, second]) == {"text": 60 + 45, "dict": 0}
    finally:
        eng.close(), store.close(), oracle.close()
    assert recomputed[0] == {}
    assert set(recomputed[1]) == set(owners)
    assert sum(not d for d in recomputed[1].values()) == 15


@pytest.mark.parametrize("k", [2, 4, 9])
def test_several_requests_of_an_owner_answer_as_the_dict_route_did(k, monkeypatch):
    """`_tree_prefixes` with the text route against the same pass with
    the splice unavailable (every tree by the dict route): each
    request's tree text and withheld rows unchanged, and the answers
    those of the sequential relay."""
    rng = random.Random(k)
    stored = [_request("team", [_stamp("team", m, m) for m in range(5)])]
    pass_ = []
    for i in range(k):
        stamps = [_stamp("team", 5 + rng.randrange(4), 100 + 10 * i + j) for j in range(2)]
        if i == k - 1:
            stamps.append(pass_[0].messages[0].timestamp)  # carried by an earlier request
        pass_.append(_request("team", stamps if i != 1 else [], "{}"))
    pass_ += [_request(f"solo-{i}", [_stamp(f"solo-{i}", 3, i)]) for i in range(3)]
    seen = {}
    real = engine_mod._tree_prefixes

    def spy(*a):
        out = real(*a)
        # the tree a diff walks (a text's `parse`, or the dict) is its text's
        assert all(dict(t) == json.loads(raw) for t, raw, _held in out.values())
        seen.setdefault("text" if "text" not in seen else "dict", {
            i: (merkle_tree_to_string(t), raw, held) for i, (t, raw, held) in out.items()})
        return out

    monkeypatch.setattr(engine_mod, "_tree_prefixes", spy)
    for route in ("text", "dict"):
        if route == "dict":
            monkeypatch.setattr(engine_mod, "fold_texts", lambda *a: None)
        store = ShardedRelayStore(":memory:", "native", shards=2)
        oracle = RelayStore(":memory:", "python")
        eng = BatchReconciler(store)
        try:
            assert _replay(store, oracle, eng, [stored, pass_])[route] == 1 + 1 + 3
        finally:
            eng.close(), store.close(), oracle.close()
    assert seen["text"] == seen["dict"] and len(seen["text"]) == k
    assert all(t == raw for t, raw, _h in seen["text"].values())


def test_equal_texts_answer_as_the_parse_and_diff_route():
    """A client text equal to the owner's answers without a parse; one
    that differs (its root equal or not) is parsed and diffed: the bytes
    of `_respond_wire`, of `_respond` encoded and of the python relay
    agree for each."""
    u = "eq-owner"
    rows = [_stamp(u, m, m) for m in range(6)]
    store = ShardedRelayStore(":memory:", "native", shards=2)
    oracle = RelayStore(":memory:", "python")
    eng = BatchReconciler(store)
    try:
        with ledger_mod.quarantine():
            eng.reconcile([_request(u, rows)])
            oracle.sync(_request(u, rows))
            text = store.get_merkle_tree_string(u)
            root = json.loads(text)["hash"]
            half = _text_of({minutes_base3((Y2023 + m) * MINUTE): 0 for m in range(3)})
            clients = [text, '{"hash":%d}' % root, json.dumps(json.loads(text)), half, "{}"]
            reqs = [_request(u, [], t) for t in clients]
            want = [protocol.encode_sync_response(oracle.sync(r)) for r in reqs]
            assert eng.reconcile_wire(reqs) == want
            assert [protocol.encode_sync_response(r) for r in eng.reconcile(reqs)] == want
        assert want[0] == want[1] == want[2] != want[4]
    finally:
        eng.close(), store.close(), oracle.close()


def test_a_pass_folds_its_owners_in_one_native_call(monkeypatch):
    """Whatever the pass's size: one call of `eh_fold_tree_texts` for the
    fold, one more only where an owner has several requests, and never
    the one-tree entry."""
    fold, many = merkle_mod._text_fold_lane()
    calls = []
    monkeypatch.setattr(merkle_mod, "_TEXT_FOLD", (
        lambda *a: calls.append("one") or fold(*a),
        lambda *a: calls.append("many") or many(*a)))
    store = ShardedRelayStore(":memory:", "native", shards=4)
    eng = BatchReconciler(store)
    try:
        with ledger_mod.quarantine():
            for n in (1, 7, 300):
                calls.clear()
                eng.reconcile([_request(f"o{n}-{i}", [_stamp(f"o{n}-{i}", i % 5, j)
                                                      for j in range(3)]) for i in range(n)])
                assert calls == ["many"]
            calls.clear()
            eng.reconcile([_request("same", [_stamp("same", 1, 1)]),
                           _request("same", [_stamp("same", 2, 2)]),
                           _request("other", [_stamp("other", 1, 1)])])
            assert calls == ["many", "many"]
    finally:
        eng.close(), store.close()
