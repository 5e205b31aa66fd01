"""Merkle trie anti-entropy digest, bit-exact with the reference.

Reference: packages/evolu/src/merkleTree.ts. A ternary trie keyed by
base-3-encoded minutes-since-epoch (truncated to int32 via JS `| 0`,
merkleTree.ts:39). Each node's hash is the XOR of murmur3 hashes of all
timestamps under that prefix; **hash values are JS signed int32** —
`undefined ^ h` and `a ^ b` in JS coerce to int32 — which this module
reproduces so serialized trees interoperate byte-for-byte with
reference replicas.

Tree representation: a dict with optional keys "hash" (signed int32)
and "0"/"1"/"2" (child dicts). Matches the reference JSON wire shape
(types.ts:80-84) directly.

The client's tree is a year's (tens of thousands of minutes, over a
megabyte of JSON) and crosses four legs a `Receive`: load, fold, store,
diff. It stays its text from one leg to the next (`MerkleText`): the
fold is one native pass from the old text to the new
(`fold_minute_deltas`), the store writes the text, and the diff compares
texts and parses only where they differ. The relay's trees are a few
minutes an owner and many a pass: a pass folds every owner's text in
one native call (`fold_texts`), and a reader parses a tree only where
the texts it compares differ.
"""

from __future__ import annotations

import json
from collections.abc import Mapping
from typing import Optional, Tuple

from evolu_tpu.core.murmur import to_int32
from evolu_tpu.core.timestamp import timestamp_to_hash
from evolu_tpu.core.types import Timestamp

MERKLE_KEY_LENGTH = 16  # base-3 digits of int32 minutes (merkleTree.ts:55-61)


def create_initial_merkle_tree() -> dict:
    return {}


def minutes_base3(millis: int) -> str:
    """merkleTree.ts:39 — `((millis/1000/60) | 0).toString(3)` (no padding)."""
    minutes = int(millis / 1000 / 60) & 0xFFFFFFFF
    if minutes >= 0x80000000:  # JS |0 is signed; millis >= 0 keeps this positive until ~year 6053
        minutes -= 0x100000000
    sign = "-" if minutes < 0 else ""  # JS Number.toString(3) keeps the sign prefix
    m = abs(minutes)
    if m == 0:
        return "0"
    digits = []
    while m:
        digits.append(str(m % 3))
        m //= 3
    return sign + "".join(reversed(digits))


def key_to_timestamp_millis(key: str) -> int:
    """merkleTree.ts:55-61 — right-pad the prefix to 16 digits, parse base 3, to millis."""
    fullkey = key + "0" * (MERKLE_KEY_LENGTH - len(key))
    return int(fullkey, 3) * 1000 * 60


def _xor(a: Optional[int], b: int) -> int:
    """JS `a ^ b` with `undefined ^ b === b | 0` (merkleTree.ts:26,45)."""
    return to_int32((a or 0) ^ b)


def insert_into_merkle_tree(t: Timestamp, tree: dict) -> dict:
    """merkleTree.ts:31-50. Returns a new tree; input is not mutated."""
    key = minutes_base3(t.millis)
    h = timestamp_to_hash(t)
    new_tree = dict(tree)
    new_tree["hash"] = _xor(tree.get("hash"), h)
    node = new_tree
    for c in key:
        child = dict(node.get(c) or {})
        child["hash"] = _xor(child.get("hash"), h)
        node[c] = child
        node = child
    return new_tree


def minute_deltas_host(timestamp_strings) -> tuple:
    """Oracle-exact host fold over timestamp STRINGS already flagged for
    insertion: → ({minute-key: int32 XOR delta}, uint32 digest). Parses
    each string and hashes its canonical re-render with the node case
    preserved VERBATIM (timestampToHash semantics) — the single shared
    implementation behind every host fallback, so client, reconcile and
    relay digests can never drift apart."""
    from evolu_tpu.core.timestamp import timestamp_from_string

    deltas: dict = {}
    digest = 0
    for s in timestamp_strings:
        t = timestamp_from_string(s)
        h = timestamp_to_hash(t)
        k = minutes_base3(t.millis)
        deltas[k] = to_int32(deltas.get(k, 0) ^ h)
        digest ^= h & 0xFFFFFFFF
    return deltas, digest


def insert_many_into_merkle_tree(timestamps, tree: dict) -> dict:
    """Batch insert (order-independent since XOR commutes). In-place on a copy."""
    for t in timestamps:
        tree = insert_into_merkle_tree(t, tree)
    return tree


def apply_prefix_xors(tree: dict, prefix_xors: dict) -> dict:
    """Apply precomputed {base3-minute-key: xor-of-hashes} deltas to a tree.

    This is the host-side half of the TPU batch insert: the device
    reduces a message batch to one XOR delta per distinct minute
    (evolu_tpu.ops.merkle_ops); applying those deltas here touches only
    O(distinct minutes * 16) nodes. Equivalent to folding
    insert_into_merkle_tree over the batch.
    """
    new_tree = dict(tree)
    for key, h in prefix_xors.items():
        # A zero delta (even number of identical hashes in the batch) must
        # still materialize the path nodes, exactly as individual inserts
        # would — so no skip here.
        new_tree["hash"] = _xor(new_tree.get("hash"), h)
        node = new_tree
        for c in key:
            child = dict(node.get(c) or {})
            child["hash"] = _xor(child.get("hash"), h)
            node[c] = child
            node = child
    return new_tree


class MinuteDeltas(Mapping):
    """A batch's per-minute XOR deltas as the device hands them over:
    `minutes` sorted distinct JS-int32 minutes (int64 array) and
    `deltas` their signed int32 XOR deltas. `fold_minute_deltas` folds
    the arrays; as a Mapping it reads as the {base3-minute-key: delta}
    dict of `apply_prefix_xors`, rendered on first use only (tests, the
    dict route)."""

    __slots__ = ("minutes", "deltas", "_keyed")

    def __init__(self, minutes, deltas):
        self.minutes = minutes
        self.deltas = deltas
        self._keyed = None

    def _by_key(self) -> dict:
        if self._keyed is None:
            self._keyed = {
                minutes_base3(m * 60000): d
                for m, d in zip(self.minutes.tolist(), self.deltas.tolist())
            }
        return self._keyed

    def __len__(self) -> int:
        return len(self.minutes)

    def __iter__(self):
        return iter(self._by_key())

    def __getitem__(self, key):
        return self._by_key()[key]


class MerkleText(Mapping):
    """A tree as its text: what `merkle_tree_to_string` wrote (the
    client's `__clock.merkleTree`, the relay's `merkleTree` row, or a
    fold's result), held as it is. The text
    is what the store writes and the wire carries, and the folds read
    and write text, so nothing parses it unless a reader asks for a
    node: as a Mapping it reads as the parsed tree, parsed on first use
    only (the diff walk, the sequential route's inserts, tests).
    `parse`, where given, makes that same tree more cheaply than a parse
    of the text (a fold of a tree already parsed), on first use too."""

    __slots__ = ("text", "_tree", "_parse")

    def __init__(self, text: str, parse=None):
        self.text = text
        self._tree = None
        self._parse = parse

    def _parsed(self) -> dict:
        if self._tree is None:
            self._tree = json.loads(self.text) if self._parse is None else self._parse()
        return self._tree

    def __len__(self) -> int:
        return len(self._parsed())

    def __iter__(self):
        return iter(self._parsed())

    def __getitem__(self, key):
        return self._parsed()[key]


_TEXT_FOLD = False  # resolved lazily: False = untried, None = unavailable


def _text_fold_lane():
    """(`eh_fold_tree_text`, `eh_fold_tree_texts`) through
    `open_gil_held`'s handle on libevolu_host.so (they read and make
    str), or None: then every fold takes the dict route."""
    global _TEXT_FOLD
    if _TEXT_FOLD is False:
        _TEXT_FOLD = None
        import ctypes as c

        from evolu_tpu.storage.native import load_library
        from evolu_tpu.utils.native_loader import open_gil_held

        plib = open_gil_held("libevolu_host.so", "eh_py_abi_probe") if load_library() else None
        if plib is not None:
            i64p = c.POINTER(c.c_int64)
            fold = plib.eh_fold_tree_text
            fold.restype = c.c_int
            fold.argtypes = [c.py_object, c.c_void_p, c.c_void_p, c.c_int64, i64p, c.py_object]
            many = plib.eh_fold_tree_texts
            many.restype = c.c_int
            many.argtypes = [c.py_object, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
                             c.c_void_p, c.py_object]
            _TEXT_FOLD = (fold, many)
    return _TEXT_FOLD


def _fold_text(text: str, minutes, deltas) -> Optional[Tuple[MerkleText, int]]:
    """The text route: one native pass from the old text to the new,
    or None where the pass refuses (or is unavailable)."""
    import ctypes
    import numpy as np

    lane = _text_fold_lane()
    if lane is None or len(minutes) != len(deltas):
        return None
    minutes = np.ascontiguousarray(minutes, np.int64)
    deltas = np.ascontiguousarray(deltas, np.int32)
    nodes = ctypes.c_int64(0)
    out: list = []
    if lane[0](text, minutes.ctypes.data, deltas.ctypes.data, len(minutes),
               ctypes.byref(nodes), out) != 0:
        return None
    return MerkleText(out[0]), nodes.value


def fold_texts(texts, starts, minutes, deltas) -> Optional[list]:
    """The text route of many trees in one native pass, which drops the
    interpreter lock once for all of them: tree j is `texts[j]` with the
    sorted distinct minutes `minutes[starts[j]:starts[j + 1]]` and their
    int32 `deltas` folded in (`starts`: len(texts) + 1 offsets from 0).
    → the new texts, None for a tree the pass refuses (a text that is
    not canonical, a minute without a 16-digit key: the dict route
    writes the same bytes); or None where the pass is unavailable."""
    import numpy as np

    lane = _text_fold_lane()
    if lane is None:
        return None
    starts = np.ascontiguousarray(starts, np.int64)
    minutes = np.ascontiguousarray(minutes, np.int64)
    deltas = np.ascontiguousarray(deltas, np.int32)
    if len(starts) != len(texts) + 1 or len(minutes) != len(deltas) or (
            len(texts) and starts[-1] != len(minutes)):
        return None
    nodes = np.empty(len(texts), np.int64)
    out: list = []
    if lane[1](texts, len(texts), starts.ctypes.data, minutes.ctypes.data,
               deltas.ctypes.data, nodes.ctypes.data, out) != 0:
        return None
    return out


def key_minutes(prefix_xors: Mapping):
    """A {base3-minute-key: delta} dict as (sorted minutes int64, their
    int32 deltas) for the text route, or None where a key has not 16
    digits 0-2 (a short or long key, a negative minute's "-")."""
    import numpy as np

    if not all(len(key) == MERKLE_KEY_LENGTH and not key.strip("012") for key in prefix_xors):
        return None
    pairs = sorted((int(key, 3), d) for key, d in prefix_xors.items())
    return (np.fromiter((m for m, _ in pairs), np.int64, count=len(pairs)),
            np.fromiter((d for _, d in pairs), np.int32, count=len(pairs)))


def _distinct_nodes(keys) -> int:
    """The root and each distinct prefix of the keys."""
    return len({key[:i] for key in keys for i in range(len(key) + 1)})


def _fold_parsed(tree: Mapping, prefix_xors: Mapping) -> Tuple[dict, int]:
    """The dict route: `apply_prefix_xors` on the parsed tree, for a
    tree that is not text, or a text or batch the native pass refuses."""
    base = tree._parsed() if isinstance(tree, MerkleText) else tree
    return apply_prefix_xors(base, prefix_xors), _distinct_nodes(prefix_xors)


def fold_minute_deltas(tree: Mapping, batch: MinuteDeltas) -> Tuple[Mapping, int]:
    """Fold a batch's sorted distinct minutes into the client's tree,
    equivalent to `insert_into_merkle_tree` over its timestamps; a zero
    delta still materializes its path. The input is not mutated.
    A `MerkleText` folds as its text (`_fold_text`) into a new
    `MerkleText`; where the native pass refuses the text or the batch
    (not canonical, a minute without a 16-digit key), and for any other
    tree, the dict route gives a dict with the same text.
    → (tree, nodes written: the root and each distinct prefix)."""
    if isinstance(tree, MerkleText):
        folded = _fold_text(tree.text, batch.minutes, batch.deltas)
        if folded is not None:
            return folded
    return _fold_parsed(tree, batch)


def fold_key_deltas(tree: Mapping, prefix_xors: Mapping) -> Tuple[Mapping, int]:
    """`fold_minute_deltas` for the host and mutate routes'
    {base3-minute-key: delta} dict: where every key has 16 digits 0-2,
    as sorted minutes (`key_minutes`); otherwise the dict route."""
    if isinstance(tree, MerkleText):
        keyed = key_minutes(prefix_xors)
        if keyed is not None:
            return fold_minute_deltas(tree, MinuteDeltas(*keyed))
    return _fold_parsed(tree, prefix_xors)


def _child_keys(tree: dict):
    # getKeys (merkleTree.ts:52-53) filters only "hash" — any other key
    # (e.g. a "-" from a negative-minutes key) participates in the walk.
    return [k for k in tree if k != "hash"]


def diff_merkle_trees(tree1: dict, tree2: dict) -> Optional[int]:
    """merkleTree.ts:63-91 — earliest minute (as millis) where trees diverge, else None.

    Walk both trees from the root; at each level take the sorted union
    of child keys and descend into the first child whose hashes differ.
    `None` (JS undefined) hash is distinct from hash 0.
    """
    if tree1.get("hash") == tree2.get("hash"):
        return None
    node1, node2 = tree1, tree2
    k = ""
    while True:
        keys = sorted(set(_child_keys(node1)) | set(_child_keys(node2)))
        diffkey = None
        for key in keys:
            next1 = node1.get(key) or {}
            next2 = node2.get(key) or {}
            if next1.get("hash") != next2.get("hash"):
                diffkey = key
                break
        if diffkey is None:
            return key_to_timestamp_millis(k)
        k += diffkey
        node1 = node1.get(diffkey) or {}
        node2 = node2.get(diffkey) or {}


def _ordered(tree: dict) -> dict:
    """Recursively order keys the way JS object property order does:

    integer-like keys ("0","1","2") ascending first, then "hash" —
    matching JSON.stringify output of the reference so serialized trees
    are byte-identical.
    """
    out = {}
    for k in ("0", "1", "2"):
        if k in tree:
            out[k] = _ordered(tree[k])
    if "hash" in tree:
        out["hash"] = tree["hash"]
    return out


def merkle_tree_to_string(tree: Mapping) -> str:
    """types.ts:80-81 — JSON with JS property order and no whitespace.
    A `MerkleText` is that text already; any other tree is rebuilt in
    that order and dumped."""
    if isinstance(tree, MerkleText):
        return tree.text
    # `_ordered` built the dict by its own recursion, so it has no cycle
    # for the encoder to look for (a third of its time).
    return json.dumps(_ordered(tree), separators=(",", ":"), check_circular=False)


def merkle_tree_from_string(s: str) -> dict:
    """types.ts:83-84."""
    return json.loads(s)
