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
diff. It keeps its canonical form from one leg to the next
(`OrderedTree`: a root whose whole tree is in JS property order, so the
store dumps it as it is) and `fold_minute_deltas` copies each distinct
node of a batch once. The relay's trees are a few minutes an owner and
many a pass: they stay plain dicts on `apply_prefix_xors`.
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


class OrderedTree(dict):
    """A ROOT whose whole tree is known to be in JS property order
    ("0","1","2" ascending, then "hash") and to hold no other key, so
    `merkle_tree_to_string` dumps it without the `_ordered` rebuild.
    Only this module's constructors make one: `fold_minute_deltas` /
    `fold_key_deltas` from a marked or empty root, and
    `ordered_tree_from_string` after one `_ordered` pass. Every other
    dict (a test's literal, the relay's trees) is unmarked and ordered
    at every dump, as before. Trees are never mutated in place."""

    __slots__ = ()


class MinuteDeltas(Mapping):
    """A batch's per-minute XOR deltas as the device hands them over:
    `minutes` sorted distinct JS-int32 minutes (int64 array) and
    `deltas` their signed int32 XOR deltas. `fold_minute_deltas` folds
    the arrays; as a Mapping it reads as the {base3-minute-key: delta}
    dict of `apply_prefix_xors`, rendered on first use only (tests, the
    per-minute routes)."""

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


# The level pass below needs every key to have 16 digits: minutes in
# [3**15, 3**16), i.e. 1997-04-13 to 2051-11-04.
_FULL_KEY_MINUTES = (3 ** (MERKLE_KEY_LENGTH - 1), 3 ** MERKLE_KEY_LENGTH)

# Fewer minutes than this take the per-minute loop. Measured on the
# chip machine's host (PR 34, call 1; consecutive minutes onto a year's
# tree, best of 200): the level pass costs 205-242 us whatever the batch
# up to 64 minutes (17 floor-divides, boundary scans, reduceats and
# tolists), the per-minute loop 23 us for one minute and about 14.5 us
# a minute after it: 206 us at 14 minutes, 233 at 16, 453 at 32.
LEVEL_PASS_MIN_MINUTES = 16

_CHILD_KEYS = ("0", "1", "2")


def _in_key_order(tree: dict) -> bool:
    """Known to be, not assumed: marked, or empty."""
    return not tree or isinstance(tree, OrderedTree)


def _xor32(h, d: int) -> int:
    """`_xor` where `d` is already a signed int32: two signed int32s XOR
    to one, so only a foreign stored hash takes the coercion."""
    if h is None:
        return d
    x = (h or 0) ^ d
    return x if -0x80000000 <= x <= 0x7FFFFFFF else to_int32(x)


def _fold_levels(tree: dict, minutes, deltas) -> Tuple[dict, int]:
    """The level pass: `minutes` sorted, distinct, all 16-digit. At
    level L (0 = root, 16 = minute) a node is a run of minutes that
    share `minute // 3**(16-L)`, and its delta is the XOR of the run.
    One descent then visits each distinct node once and copies it once,
    children before "hash". → (root dict, nodes copied)."""
    import numpy as np

    depth = MERKLE_KEY_LENGTH
    # Bottom up: level L's prefixes are level L+1's // 3, and its runs
    # are unions of level L+1's runs.
    prefix = minutes
    starts = np.arange(len(minutes))
    digits = [None] * (depth + 1)  # per level: each node's key digit
    node_delta = [None] * (depth + 1)  # per level: each node's delta
    first_child = [None] * depth  # per level: node i's children are [fc[i], fc[i+1])
    nodes = 0
    for level in range(depth, -1, -1):
        if level < depth:
            parent = prefix // 3
            run = np.flatnonzero(parent[1:] != parent[:-1]) + 1
            child_first = np.concatenate(([0], run))  # into level+1's nodes
            first_child[level] = np.append(child_first, len(prefix)).tolist()
            starts = starts[child_first]
            prefix = parent[child_first]
        digits[level] = [_CHILD_KEYS[k] for k in (prefix % 3).tolist()]
        node_delta[level] = np.bitwise_xor.reduceat(deltas, starts).tolist()
        nodes += len(starts)

    def visit(level: int, i: int, old) -> dict:
        new = {}
        if level < depth:
            lo, hi = first_child[level][i], first_child[level][i + 1]
            below, digs = level + 1, digits[level + 1]
        else:  # a minute: no child of the batch's, but after 2051 a
            lo = hi = 0  # stored minute is also a prefix of 17-digit keys
        if old:
            for k in _CHILD_KEYS:
                if lo < hi and digs[lo] == k:
                    new[k] = visit(below, lo, old.get(k))
                    lo += 1
                elif k in old:
                    new[k] = old[k]
        else:
            for j in range(lo, hi):
                new[digs[j]] = visit(below, j, None)
        new["hash"] = _xor32(old.get("hash") if old else None, node_delta[level][i])
        return new

    return visit(0, 0, tree), nodes


def _put_ordered(parent, c, child, h: int) -> dict:
    """A copy of `parent` (or a new node where it is None or empty) with
    `h` XORed into its hash and, unless `c` is None, `child` at key `c`;
    keys in JS property order."""
    new = {}
    for k in _CHILD_KEYS:
        if k == c:
            new[k] = child
        elif parent and k in parent:
            new[k] = parent[k]
    new["hash"] = _xor(parent.get("hash") if parent else None, h)
    return new


def fold_key_deltas(tree: dict, prefix_xors: Mapping) -> Tuple[dict, int]:
    """`apply_prefix_xors` for the client's tree: the same fold, one
    path a minute, but a marked (or empty) root stays marked, because
    every node it copies is written children first, "hash" last. An
    unmarked root, or a key with another character than 0, 1, 2 (a
    negative minute's "-", which `_ordered` would drop at the dump),
    takes `apply_prefix_xors` itself and comes back unmarked.
    → (tree, nodes copied)."""
    nodes = sum(len(key) + 1 for key in prefix_xors)
    if not _in_key_order(tree) or any(key.strip("012") for key in prefix_xors):
        return apply_prefix_xors(tree, prefix_xors), nodes
    root = tree
    for key, h in prefix_xors.items():
        path = []  # the old node at each prefix of the key, root first
        node = root
        for c in key:
            path.append(node)
            node = node.get(c) if node else None
        new = _put_ordered(node, None, None, h)
        for c, parent in zip(reversed(key), reversed(path)):
            new = _put_ordered(parent, c, new, h)
        root = new
    return OrderedTree(root), nodes


def fold_minute_deltas(tree: dict, batch: MinuteDeltas) -> Tuple[dict, int]:
    """Fold a batch's sorted distinct minutes into the client's tree,
    equivalent to `insert_into_merkle_tree` over its timestamps. The
    input is not mutated and untouched subtrees stay the same objects.
    Adapts to what the batch shows: the level pass where every key has
    16 digits and there are enough minutes to pay numpy's fixed cost,
    else the per-minute loop. A zero delta still materializes its path.
    → (tree, nodes copied): marked `OrderedTree` from a marked or empty
    root, a plain dict otherwise."""
    n = len(batch)
    if n == 0:
        return tree, 0
    lo, hi = _FULL_KEY_MINUTES
    if (
        n < LEVEL_PASS_MIN_MINUTES
        or not _in_key_order(tree)
        or not (lo <= batch.minutes[0] and batch.minutes[-1] < hi)
    ):
        return fold_key_deltas(tree, batch)
    root, nodes = _fold_levels(tree, batch.minutes, batch.deltas)
    return OrderedTree(root), nodes


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


def merkle_tree_to_string(tree: dict) -> str:
    """types.ts:80-81 — JSON with JS property order and no whitespace.
    An `OrderedTree` is in that order already; any other dict is
    rebuilt in it first."""
    if not isinstance(tree, OrderedTree):
        tree = _ordered(tree)
    # Either way the tree was built by this module's own recursion, so
    # it has no cycle for the encoder to look for (a third of its time).
    return json.dumps(tree, separators=(",", ":"), check_circular=False)


def merkle_tree_from_string(s: str) -> dict:
    """types.ts:83-84."""
    return json.loads(s)


def ordered_tree_from_string(s: str) -> OrderedTree:
    """`merkle_tree_from_string` for a tree that will be folded and
    stored again (the client's `__clock`): ordered once here, whoever
    wrote the text, and marked."""
    return OrderedTree(_ordered(json.loads(s)))
