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


# --- the client's fold: text to text, the dict route beside it ---

import copy  # noqa: E402
import functools  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from evolu_tpu.core.merkle import (  # noqa: E402
    MerkleText,
    MinuteDeltas,
    fold_key_deltas,
    fold_minute_deltas,
)
from evolu_tpu.ops.merkle_ops import decode_minute_delta_arrays  # noqa: E402

MINUTE = 60_000
Y2023 = 1_700_000_040_000  # minute-aligned, a 16-digit key
FULL_KEYS = (3 ** 15 * MINUTE, 3 ** 16 * MINUTE)  # 1997-04-13 .. 2051-11-04


def _js_minute(millis: int) -> int:
    m = int(millis / 1000 / 60) & 0xFFFFFFFF
    return m - 0x100000000 if m >= 0x80000000 else m


def _stamps(minutes, rng, per_minute=(1, 3)):
    """1-3 timestamps in each of `minutes` (millis of the minute's start)."""
    return [Timestamp(m + rng.randrange(MINUTE), rng.randrange(4), "00000000000000%02x" % rng.randrange(8))
            for m in minutes for _ in range(rng.randint(*per_minute))]


def _sessions(days, rng, first_day=0):
    """`days` days of three 30-minute sessions: days x 90 distinct minutes."""
    return [Y2023 + (d * 1440 + s * 480 + start + k) * MINUTE
            for d in range(first_day, first_day + days) for s in range(3)
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


def _fuzz_tree(rng):
    """A random tree in canonical form, as any writer of `__clock` could
    leave it: 16-digit minutes and a few 17-digit ones below them, hashes
    anywhere in int32, some nodes without a hash, some empty children."""
    lo, hi = FULL_KEYS
    base = rng.randrange(lo, hi - 400_000 * MINUTE) // MINUTE
    keys = {minutes_base3((base + rng.randrange(400_000)) * MINUTE): rng.choice(
        [0, -2 ** 31, 2 ** 31 - 1, rng.randrange(-2 ** 31, 2 ** 31)]) for _ in range(rng.randrange(1, 400))}
    tree = apply_prefix_xors({}, keys)
    leaves = sorted(keys)
    for key in rng.sample(leaves, min(3, len(leaves))):  # a 17-digit key below a 16-digit one
        node = tree
        for c in key:
            node = node[c]
        node[rng.choice("012")] = {"hash": rng.randrange(-2 ** 31, 2 ** 31)}

    def walk(node, depth):
        if depth and rng.random() < 0.05:
            node.pop("hash", None)
        for c in "012":
            if c in node:
                walk(node[c], depth + 1)
            elif depth < 16 and rng.random() < 0.01:
                node[c] = {}
    walk(tree, 0)
    return tree, base


def _fold_case(name):
    """→ (initial tree, its text as the text route starts from it,
    batches of Timestamps, whether the text route's first fold is
    refused: the text is not canonical or a minute has no 16-digit key)."""
    rng = random.Random(34)
    if name == "empty-batch":
        tree = apply_prefix_xors({}, _keyed(_stamps(_sessions(1, rng), rng)))
        return tree, merkle_tree_to_string(tree), [[]], False
    if name == "year-in-four-batches":
        minutes = _sessions(100, rng)  # 9,000 minutes
        assert len(set(minutes)) >= 8000
        stamps = _stamps(minutes, rng)
        q = len(stamps) // 4
        return {}, "{}", [stamps[:q], stamps[q:2 * q], stamps[2 * q:3 * q], stamps[3 * q:]], False
    if name == "8212-minutes-onto-a-year":  # a restore's last Receive in client-todo-months
        minutes = _sessions(365, rng)
        q = len(minutes) * 3 // 4
        tree = {}
        for t in _stamps(minutes[:q], rng, (1, 1)):
            tree = insert_into_merkle_tree(t, tree)
        batch = _stamps(minutes[q - 2:], rng, (1, 1))
        assert len({minutes_base3(t.millis) for t in batch}) >= 8212
        return tree, merkle_tree_to_string(tree), [batch], False
    if name == "one-minute":
        return {}, "{}", [_stamps([Y2023], rng, (3, 3))], False
    if name == "straddle":  # the last minute of a batch is the first of the next
        minutes = _sessions(1, rng)
        a, b = _stamps(minutes[:46], rng, (2, 2)), _stamps(minutes[45:], rng, (2, 2))
        return {}, "{}", [a, b], False
    if name == "zero-delta-one-minute":
        t = Timestamp(Y2023 + 5, 1, "0000000000000001")
        return {}, "{}", [[t, t]], False
    if name == "zero-delta-among-many":
        t = Timestamp(Y2023 + 77 * MINUTE + 5, 1, "0000000000000001")
        return {}, "{}", [_stamps(_sessions(1, rng), rng) + [t, t]], False
    if name == "hash-of-minus-2-to-the-31":
        stamps = _stamps(_sessions(1, rng), rng)
        tree = apply_prefix_xors({}, {minutes_base3(stamps[0].millis): -2 ** 31,
                                      minutes_base3(Y2023 + 3 * 1440 * MINUTE): -2 ** 31})
        assert '"hash":-2147483648' in merkle_tree_to_string(tree)
        return tree, merkle_tree_to_string(tree), [stamps, _stamps([Y2023 + 3 * 1440 * MINUTE], rng)], False
    if name == "pre-1997-and-post-2051-minutes":  # 0, under 3**15, at and over 3**16
        lo, hi = FULL_KEYS
        minutes = [0, 5 * MINUTE, lo - MINUTE, lo, hi - MINUTE, hi, hi + MINUTE]
        return {}, "{}", [_stamps(minutes, rng), _stamps(minutes + _sessions(1, rng), rng)], True
    if name == "negative-minutes":  # a "-" key: `_ordered` drops it at the dump
        minutes = [-5 * MINUTE, 2 ** 31 * MINUTE + 2 * MINUTE, Y2023]
        return {}, "{}", [_stamps(minutes, rng), _stamps(minutes + _sessions(1, rng), rng)], True
    if name in ("text-hash-first", "text-with-whitespace", "text-with-a-dash-key"):
        tree = {}
        for t in _stamps(_sessions(2, rng), rng):
            tree = insert_into_merkle_tree(t, tree)  # "hash" before the children
        assert list(tree)[0] == "hash"
        if name == "text-hash-first":
            text = json.dumps(tree, separators=(",", ":"))
        elif name == "text-with-whitespace":
            text = json.dumps(json.loads(merkle_tree_to_string(tree)))
        else:
            tree = {"-": {"hash": 5}, **json.loads(merkle_tree_to_string(tree))}
            text = json.dumps(tree, separators=(",", ":"))
        return tree, text, [_stamps(_sessions(3, rng), rng), _stamps([Y2023], rng)], True
    if name.startswith("fuzz-"):
        rng = random.Random(int(name[len("fuzz-"):]))
        tree, base = _fuzz_tree(rng)
        batches = [_stamps([(base + rng.randrange(400_000)) * MINUTE
                            for _ in range(rng.randrange(0, 300))], rng) for _ in range(3)]
        return tree, merkle_tree_to_string(tree), batches, False
    raise AssertionError(name)


FOLD_CASES = ["empty-batch", "year-in-four-batches", "8212-minutes-onto-a-year", "one-minute",
              "straddle", "zero-delta-one-minute", "zero-delta-among-many",
              "hash-of-minus-2-to-the-31", "pre-1997-and-post-2051-minutes", "negative-minutes",
              "text-hash-first", "text-with-whitespace", "text-with-a-dash-key",
              "fuzz-1", "fuzz-2", "fuzz-3"]


@functools.lru_cache(maxsize=None)
def _insert_texts(case):
    """`merkle_tree_to_string` of `insert_into_merkle_tree` folded over
    each batch in turn: the reference, after every batch."""
    tree, _text, batches, _refused = _fold_case(case)
    texts = []
    for batch in batches:
        for t in batch:
            tree = insert_into_merkle_tree(t, tree)
        texts.append(merkle_tree_to_string(tree))
    return texts


@pytest.mark.parametrize("start", ["text", "dict"])
@pytest.mark.parametrize("route", ["arrays", "keys"])
@pytest.mark.parametrize("case", FOLD_CASES)
def test_ordered_fold_equals_insert_text_for_text(case, route, start):
    """`fold_minute_deltas` over the decoded segments (and `fold_key_deltas`
    over the host routes' dict) against `insert_into_merkle_tree` folded
    over the same timestamps, after every batch, through
    `merkle_tree_to_string`, from the tree's text (the text route: the
    native pass, or the dict route where it refuses) and from the tree
    as a dict (the dict route). The nodes counted are the batch's
    distinct nodes, whichever route wrote them."""
    tree, text, batches, refused = _fold_case(case)
    if start == "text":
        tree = MerkleText(text)
    rng = random.Random(7)
    for batch, want in zip(batches, _insert_texts(case)):
        as_text = isinstance(tree, MerkleText)
        before = tree.text if as_text else copy.deepcopy(tree)
        if route == "arrays":
            deltas = decode_minute_delta_arrays(*_segments(batch, rng))
            assert isinstance(deltas, MinuteDeltas) and deltas == _keyed(batch)
            assert (np.diff(deltas.minutes) > 0).all()  # sorted, distinct
            folded, nodes = fold_minute_deltas(tree, deltas)
        else:
            folded, nodes = fold_key_deltas(tree, _keyed(batch))
        assert (tree.text if as_text else tree) == before  # not mutated
        tree = folded
        assert merkle_tree_to_string(tree) == want
        assert isinstance(tree, MerkleText) == (start == "text" and not refused)
        if isinstance(tree, MerkleText):
            assert json.loads(tree.text) == json.loads(want)
        keys = {minutes_base3(t.millis) for t in batch}
        assert nodes == len({key[:i] for key in keys for i in range(len(key) + 1)})
    if case.startswith("zero-delta-one"):
        node = tree  # the path is there, every hash on it 0
        for c in minutes_base3(Y2023):
            assert node["hash"] == 0
            node = node[c]
        assert node == {"hash": 0}


def test_a_text_is_parsed_only_when_a_node_is_read(monkeypatch):
    """`MerkleText` reads as the parsed tree, parsed once on first use;
    a fold, `merkle_tree_to_string` and the text itself never parse."""
    rng = random.Random(9)
    stamps = _stamps(_sessions(2, rng), rng)
    plain = {}
    for t in stamps:
        plain = insert_into_merkle_tree(t, plain)
    text = merkle_tree_to_string(plain)
    parses = []
    real = json.loads
    monkeypatch.setattr(json, "loads", lambda s, *a, **k: parses.append(len(s)) or real(s, *a, **k))
    tree, _ = fold_key_deltas(MerkleText("{}"), _keyed(stamps))
    assert isinstance(tree, MerkleText) and tree.text == text
    assert merkle_tree_to_string(tree) is tree.text and parses == []
    assert tree == plain and tree["hash"] == plain["hash"] and parses == [len(text)]
    assert diff_merkle_trees(plain, tree) is None and sorted(tree) == sorted(plain)
    assert parses == [len(text)]
    # apply_prefix_xors keeps its contract: a dict out, whatever comes in.
    assert type(apply_prefix_xors(tree, {})) is dict


_Y2023_MINUTE = Y2023 // MINUTE
_DEEP = '{"0":' * 21 + '{"hash":1}' + "}" * 21


@pytest.mark.parametrize("text, minutes", [
    ('{"hash":1,"0":{"hash":1}}', [_Y2023_MINUTE]),  # "hash" before a child
    ('{"0": {"hash":1},"hash":1}', [_Y2023_MINUTE]),  # whitespace
    ('{"hash":1} ', [_Y2023_MINUTE]),  # trailing whitespace
    ('{"-":{"hash":1},"hash":1}', [_Y2023_MINUTE]),  # a negative minute's key
    ('{"3":{"hash":1},"hash":1}', [_Y2023_MINUTE]),  # not a base-3 digit
    ('{"1":{"hash":1},"0":{"hash":1}}', [_Y2023_MINUTE]),  # children out of order
    ('{"0":{"hash":1},"0":{"hash":2}}', [_Y2023_MINUTE]),  # a child twice
    ('{"hash":2147483648}', [_Y2023_MINUTE]),  # over int32
    ('{"hash":-2147483649}', [_Y2023_MINUTE]),  # under int32
    ('{"hash":-0}', [_Y2023_MINUTE]),  # not how JSON.stringify writes 0
    (_DEEP, [_Y2023_MINUTE]),  # deeper than any int32 minute's key
    ('{}', [3 ** 15 - 1]),  # before 1997-04-13: a 15-digit key
    ('{}', [3 ** 16]),  # from 2051-11-04: a 17-digit key
    ('{}', [_Y2023_MINUTE + 1, _Y2023_MINUTE]),  # minutes out of order
], ids=["hash-first", "whitespace", "trailing-space", "dash-key", "digit-3", "order", "twice",
        "over-int32", "under-int32", "minus-zero", "too-deep", "pre-1997", "post-2051", "unsorted"])
def test_the_native_pass_refuses_what_it_does_not_recognise(text, minutes):
    """Every refusal of `eh_fold_tree_text`: the pass returns no text, and
    the fold takes the dict route, which writes what
    `apply_prefix_xors` on the parsed text writes."""
    from evolu_tpu.core.merkle import _fold_text
    from evolu_tpu.storage import native

    if not native.native_available():
        pytest.skip("the text route needs the native library")
    batch = MinuteDeltas(np.array(minutes, np.int64), np.arange(1, len(minutes) + 1, dtype=np.int32))
    assert _fold_text(text, batch.minutes, batch.deltas) is None
    folded, nodes = fold_minute_deltas(MerkleText(text), batch)
    assert type(folded) is dict
    assert merkle_tree_to_string(folded) == merkle_tree_to_string(
        apply_prefix_xors(json.loads(text), dict(batch)))
    assert nodes == len({key[:i] for key in batch for i in range(len(key) + 1)})
