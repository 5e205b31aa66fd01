"""Merkle trie golden tests.

Expected values ported from the reference's vitest snapshots
(packages/evolu/test/merkleTree.test.ts +
__snapshots__/merkleTree.test.ts.snap). Hashes are JS signed int32
(XOR coercion), serialization matches JS JSON.stringify property order.
"""

import json
import random

from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    create_initial_merkle_tree,
    diff_merkle_trees,
    insert_into_merkle_tree,
    key_to_timestamp_millis,
    merkle_tree_from_string,
    merkle_tree_to_string,
    minutes_base3,
)
from evolu_tpu.core.timestamp import timestamp_to_hash
from evolu_tpu.core.murmur import to_int32
from evolu_tpu.core.types import Timestamp


def node1(millis=0, counter=0):
    return Timestamp(millis, counter, "0000000000000001")


def test_create_initial_merkle_tree():
    assert create_initial_merkle_tree() == {}


def test_insert_single_at_epoch():
    # snapshot `insertIntoMerkleTree 1`
    tree = insert_into_merkle_tree(node1(), {})
    assert tree == {"hash": -1416139081, "0": {"hash": -1416139081}}


def test_insert_single_2022():
    # snapshot `insertIntoMerkleTree 2` — ts 1656873738591, 16-digit base-3 key
    tree = insert_into_merkle_tree(node1(1656873738591), {})
    assert tree["hash"] == -468843282
    key = minutes_base3(1656873738591)
    # Path read off the snapshot's nesting: 1→2→2→0→2→2→1→2→2→2→0→0→1→1→2→0
    assert key == "1220221222001120"
    node = tree
    for c in key:
        node = node[c]
        assert node["hash"] == -468843282
    assert "0" not in node and "1" not in node and "2" not in node


def test_insert_both_and_order_independence():
    # snapshot `insertIntoMerkleTree 3` — root hash is XOR of both
    ts1, ts2 = node1(), node1(1656873738591)
    t_a = insert_into_merkle_tree(ts2, insert_into_merkle_tree(ts1, {}))
    t_b = insert_into_merkle_tree(ts1, insert_into_merkle_tree(ts2, {}))
    assert t_a == t_b
    assert t_a["hash"] == 1335454297
    assert t_a["0"]["hash"] == -1416139081


def test_diff_merkle_trees():
    assert diff_merkle_trees({}, {}) is None
    mt = insert_into_merkle_tree(node1(1656873738591), {})
    # snapshot `diffMerkleTrees 2` — minute floor of the inserted ts
    assert diff_merkle_trees({}, mt) == 1656873720000
    assert diff_merkle_trees({}, mt) == diff_merkle_trees(mt, {})


def test_diff_detects_divergence_minute():
    # Modern millis ⇒ full 16-digit keys ⇒ diff pinpoints the exact minute.
    # (Tiny millis produce short, right-padded keys — a reference quirk we
    # reproduce: see keyToTimestamp right-padding, merkleTree.ts:55-61.)
    t0 = 1656873720000  # minute-aligned
    base = {}
    for m in [t0, t0 + 60000, t0 + 120000, t0 + 600000]:
        base = insert_into_merkle_tree(node1(m), base)
    other = insert_into_merkle_tree(node1(t0 + 120000, 1), base)
    assert diff_merkle_trees(base, other) == t0 + 120000


def test_key_to_timestamp_millis():
    assert key_to_timestamp_millis("") == 0
    assert key_to_timestamp_millis(minutes_base3(1656873720000)) == 1656873720000


def test_serialization_matches_js_json():
    tree = insert_into_merkle_tree(
        node1(), insert_into_merkle_tree(node1(1656873738591), {})
    )
    s = merkle_tree_to_string(tree)
    # JS property order: numeric keys ascending first, then "hash".
    assert s.startswith('{"0":{"hash":-1416139081}')
    assert merkle_tree_from_string(s) == tree
    # No whitespace (JSON.stringify default).
    assert " " not in s


def test_hash_zero_vs_missing_distinct():
    # undefined !== 0 in the diff walk.
    t1 = {"hash": 0, "0": {"hash": 0}}
    t2 = {}
    assert diff_merkle_trees(t1, t2) is not None


def test_apply_prefix_xors_equivalence():
    rng = random.Random(42)
    timestamps = [
        Timestamp(rng.randrange(0, 2**41), rng.randrange(0, 65536), "0000000000000001")
        for _ in range(200)
    ]
    seq = {}
    for t in timestamps:
        seq = insert_into_merkle_tree(t, seq)

    # Batch: aggregate XOR per full 16-level prefix chain, like the TPU path.
    deltas = {}
    for t in timestamps:
        key = minutes_base3(t.millis)
        h = timestamp_to_hash(t)
        deltas[key] = to_int32(deltas.get(key, 0) ^ h)
    batched = apply_prefix_xors({}, deltas)
    assert batched == seq


# --- the client's fold: a distinct node once, key order kept (ISSUE 34) ---

import copy  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from evolu_tpu.core import merkle  # noqa: E402
from evolu_tpu.core.merkle import (  # noqa: E402
    MinuteDeltas,
    OrderedTree,
    fold_key_deltas,
    fold_minute_deltas,
    ordered_tree_from_string,
)
from evolu_tpu.ops.merkle_ops import decode_minute_delta_arrays  # noqa: E402

MINUTE = 60_000
Y2023 = 1_700_000_040_000  # minute-aligned, a 16-digit key


def _js_minute(millis: int) -> int:
    m = int(millis / 1000 / 60) & 0xFFFFFFFF
    return m - 0x100000000 if m >= 0x80000000 else m


def _stamps(minutes, rng, per_minute=(1, 3)):
    """1-3 timestamps in each of `minutes` (millis of the minute's start)."""
    return [Timestamp(m + rng.randrange(MINUTE), rng.randrange(4), "00000000000000%02x" % rng.randrange(8))
            for m in minutes for _ in range(rng.randint(*per_minute))]


def _sessions(days, rng):
    """`days` days of three 30-minute sessions: days x 90 distinct minutes."""
    return [Y2023 + (d * 1440 + s * 480 + start + k) * MINUTE
            for d in range(days) for s in range(3)
            for start in [rng.randrange(440)] for k in range(30)]


def _segments(batch, rng):
    """A batch as the plan kernel's pulled segments: a row a timestamp,
    shuffled, so minutes repeat and come unsorted (tile-local partials),
    plus masked rows that must be ignored."""
    rows = [(_js_minute(t.millis), timestamp_to_hash(t) & 0xFFFFFFFF) for t in batch]
    rng.shuffle(rows)
    minute = np.array([m for m, _ in rows] + [0, 0], np.int32)
    xor = np.array([h for _, h in rows] + [7, 9], np.uint32)
    seg_end = np.array([True] * len(rows) + [True, False])
    valid = np.array([True] * len(rows) + [False, True])
    return minute, seg_end, xor, valid


def _keyed(batch):
    deltas = {}
    for t in batch:
        k = minutes_base3(t.millis)
        deltas[k] = to_int32(deltas.get(k, 0) ^ timestamp_to_hash(t))
    return deltas


def _fold_case(name):
    """→ (initial tree, batches of Timestamps, the result is marked)."""
    rng = random.Random(34)
    if name == "year-in-four-batches":
        minutes = _sessions(100, rng)  # 9,000 minutes
        assert len(set(minutes)) >= 8000
        stamps = _stamps(minutes, rng)
        q = len(stamps) // 4
        return {}, [stamps[:q], stamps[q:2 * q], stamps[2 * q:3 * q], stamps[3 * q:]], True
    if name == "one-minute":
        return {}, [_stamps([Y2023], rng, (3, 3))], True
    if name == "straddle":  # the last minute of a batch is the first of the next
        minutes = _sessions(1, rng)
        a, b = _stamps(minutes[:46], rng, (2, 2)), _stamps(minutes[45:], rng, (2, 2))
        return {}, [a, b], True
    if name == "zero-delta-few":  # the per-minute route
        t = Timestamp(Y2023 + 5, 1, "0000000000000001")
        return {}, [[t, t]], True
    if name == "zero-delta-many":  # the level pass
        t = Timestamp(Y2023 + 77 * MINUTE + 5, 1, "0000000000000001")
        return {}, [_stamps(_sessions(1, rng), rng) + [t, t]], True
    if name == "short-and-long-keys":  # 0, under 3**15, at and over 3**16: the per-minute route
        lo, hi = 3 ** 15 * MINUTE, 3 ** 16 * MINUTE
        minutes = [0, 5 * MINUTE, lo - MINUTE, lo, hi - MINUTE, hi, hi + MINUTE]
        return {}, [_stamps(minutes, rng), _stamps(minutes + _sessions(1, rng), rng)], True
    if name == "negative-minutes":  # a "-" key: `_ordered` drops it at the dump, so never marked
        minutes = [-5 * MINUTE, 2 ** 31 * MINUTE + 2 * MINUTE, Y2023]
        return {}, [_stamps(minutes, rng), _stamps(minutes + _sessions(1, rng), rng)], False
    if name == "hand-built-wrong-order":  # "hash" before the children, as insert writes it
        first = _stamps(_sessions(2, rng), rng)
        tree = {}
        for t in first:
            tree = insert_into_merkle_tree(t, tree)
        assert list(tree)[0] == "hash" and not isinstance(tree, OrderedTree)
        return tree, [_stamps(_sessions(3, rng), rng), _stamps([Y2023], rng)], False
    raise AssertionError(name)


FOLD_CASES = ["year-in-four-batches", "one-minute", "straddle", "zero-delta-few",
              "zero-delta-many", "short-and-long-keys", "negative-minutes",
              "hand-built-wrong-order"]


@pytest.mark.parametrize("route", ["arrays", "keys"])
@pytest.mark.parametrize("case", FOLD_CASES)
def test_ordered_fold_equals_insert_text_for_text(case, route):
    """`fold_minute_deltas` over the decoded segments (and `fold_key_deltas`
    over the host routes' dict) against `insert_into_merkle_tree` folded
    over the same timestamps, after every batch, through
    `merkle_tree_to_string`; a marked result dumps straight to that text."""
    tree, batches, marked = _fold_case(case)
    rng = random.Random(7)
    want = tree
    for batch in batches:
        for t in batch:
            want = insert_into_merkle_tree(t, want)
        before = copy.deepcopy(tree)
        if route == "arrays":
            deltas = decode_minute_delta_arrays(*_segments(batch, rng))
            assert isinstance(deltas, MinuteDeltas) and deltas == _keyed(batch)
            assert (np.diff(deltas.minutes) > 0).all()  # sorted, distinct
            folded, nodes = fold_minute_deltas(tree, deltas)
        else:
            folded, nodes = fold_key_deltas(tree, _keyed(batch))
        assert tree == before  # the input is not mutated
        tree = folded
        text = merkle_tree_to_string(want)
        assert merkle_tree_to_string(tree) == text
        assert isinstance(tree, OrderedTree) == marked
        if marked:
            assert json.dumps(tree, separators=(",", ":")) == text
        assert nodes >= len({minutes_base3(t.millis) for t in batch}) + 16
    if case == "zero-delta-few":
        node = tree  # the path is there, every hash on it 0
        for c in minutes_base3(Y2023):
            assert node["hash"] == 0
            node = node[c]
        assert node == {"hash": 0}


def test_level_pass_copies_each_distinct_node_once_and_shares_the_rest():
    rng = random.Random(3)
    first, second = _sessions(40, rng)[:1800], [Y2023 + (400 * 1440 + k) * MINUTE for k in range(60)]
    base, _ = fold_minute_deltas({}, decode_minute_delta_arrays(*_segments(_stamps(first, rng), rng)))
    batch = _stamps(second, rng)
    deltas = decode_minute_delta_arrays(*_segments(batch, rng))
    assert len(deltas) >= merkle.LEVEL_PASS_MIN_MINUTES
    snapshot = copy.deepcopy(base)
    tree, nodes = fold_minute_deltas(base, deltas)
    assert base == snapshot and tree is not base
    # One copy a distinct node: the root and every distinct prefix of the keys.
    keys = {minutes_base3(t.millis) for t in batch}
    assert nodes == 1 + len({k[:i] for k in keys for i in range(1, 17)})
    assert nodes < 17 * len(keys) / 4  # where the per-minute loop copies 17 a minute
    # Subtrees the batch does not enter are the same objects, not copies.
    touched = {k[:i] for k in keys for i in range(17)}
    shared = 0

    def walk(old, new, prefix):
        nonlocal shared
        for c in "012":
            if c in old:
                if prefix + c in touched:
                    assert new[c] is not old[c]
                    walk(old[c], new[c], prefix + c)
                else:
                    assert new[c] is old[c]
                    shared += 1
    walk(base, tree, "")
    assert shared > 0


def test_a_few_minutes_take_the_per_minute_loop_and_many_the_level_pass(monkeypatch):
    calls = []
    real = merkle._fold_levels
    monkeypatch.setattr(merkle, "_fold_levels", lambda *a: calls.append(len(a[1])) or real(*a))
    few = MinuteDeltas(np.arange(3, dtype=np.int64) + Y2023 // MINUTE, np.array([1, 2, 3], np.int32))
    n = merkle.LEVEL_PASS_MIN_MINUTES
    many = MinuteDeltas(np.arange(n, dtype=np.int64) + Y2023 // MINUTE, np.arange(n, dtype=np.int32))
    early = MinuteDeltas(np.arange(n, dtype=np.int64) + 3 ** 15 - 1, np.arange(n, dtype=np.int32))
    tree, nodes = fold_minute_deltas({}, few)
    assert calls == [] and nodes == 3 * 17
    tree, _ = fold_minute_deltas(tree, many)
    assert calls == [n]
    tree, _ = fold_minute_deltas(tree, early)  # one 15-digit key among them
    assert calls == [n]
    assert isinstance(tree, OrderedTree)
    assert fold_minute_deltas(tree, MinuteDeltas(few.minutes[:0], few.deltas[:0])) == (tree, 0)


def test_a_marked_root_never_reaches_ordered_and_an_unmarked_one_always_does(monkeypatch):
    rng = random.Random(9)
    stamps = _stamps(_sessions(2, rng), rng)
    marked, _ = fold_minute_deltas({}, decode_minute_delta_arrays(*_segments(stamps, rng)))
    plain = {}
    for t in stamps:
        plain = insert_into_merkle_tree(t, plain)
    text = merkle_tree_to_string(plain)
    seen = []
    real = merkle._ordered
    monkeypatch.setattr(merkle, "_ordered", lambda t: seen.append(t) or real(t))
    assert merkle_tree_to_string(marked) == text and seen == []
    assert merkle_tree_to_string(plain) == text and seen[0] is plain
    # A dict that merely looks ordered is not trusted: only the marker is.
    del seen[:]
    assert merkle_tree_to_string(dict(marked)) == text and len(seen) > 0
    # A foreign text is ordered once at the parse, then marked.
    foreign = json.dumps(plain, separators=(",", ":"))  # "hash" first: not JS order
    assert foreign != text
    del seen[:]
    parsed = ordered_tree_from_string(foreign)
    assert isinstance(parsed, OrderedTree) and len(seen) > 0
    del seen[:]
    assert merkle_tree_to_string(parsed) == text and seen == []
    # apply_prefix_xors keeps its contract: plain in, plain out, whatever comes in.
    assert not isinstance(apply_prefix_xors(marked, {}), OrderedTree)
