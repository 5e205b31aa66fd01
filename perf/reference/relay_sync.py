"""The plain reference of `relay-mesh4`: the reference relay's `sync`
(`apps/server/src/index.ts:121-216`), one request and one message at a
time over stdlib `sqlite3`.

`sync` is `addMessages` then `getMessages` (index.ts:204-216). For every
message of a request: `INSERT OR IGNORE` on (timestamp, userId), and
only where that changed a row the timestamp's hash is XORed into the
owner's tree; the tree is then stored with `INSERT OR REPLACE`
(index.ts:138-171). The answer is every stored row of the owner after
the first minute in which the stored tree and the caller's differ, but
for rows whose timestamp ends in the caller's node (index.ts:173-202,
the filter at :100), and the stored tree's string.

Nothing here is the program's. The timestamp's string form, the tree's
insert and its string form are `perf/reference/client_todo.py`'s (named
here, not copied: `tests/test_client_restore_reference.py` holds them to
upstream's snapshots), the hash is the benchmark's own numpy murmur3
behind them, and the diff (`merkleTree.ts:52-91`) and the sync
timestamp (`timestamp.ts:35-41`) are written out below.
"""

import json
import sqlite3

from perf import load_module

_client = load_module("reference", "client_todo")
parse_timestamp = _client.parse_timestamp
render_timestamp = _client.render_timestamp
timestamp_hashes = _client.timestamp_hashes
tree_insert = _client.tree_insert
tree_to_string = _client.tree_to_string

KEY_LENGTH = 16  # merkleTree.ts:55-61: a minute is 16 base-3 digits
SYNC_NODE = "0" * 16  # timestamp.ts:35-41


def key_to_millis(key: str) -> int:
    """`keyToTimestamp` (merkleTree.ts:55-61): the prefix padded right
    with zeros to a whole minute key, read in base 3, as millis."""
    return int(key.ljust(KEY_LENGTH, "0"), 3) * 60_000


def tree_diff(tree1: dict, tree2: dict):
    """`diffMerkleTrees` (merkleTree.ts:63-91): the millis of the first
    minute in which the trees differ, or None where their root hashes
    are equal. A missing hash is JS's `undefined`, which equals only
    another missing hash."""
    if tree1.get("hash") == tree2.get("hash"):
        return None
    node1, node2, key = tree1, tree2, ""
    while True:
        digits = sorted({k for k in (*node1, *node2) if k != "hash"})
        for digit in digits:
            child1, child2 = node1.get(digit) or {}, node2.get(digit) or {}
            if child1.get("hash") != child2.get("hash"):
                break
        else:
            return key_to_millis(key)
        key += digit
        node1, node2 = child1, child2


class ReferenceRelay:
    """One relay: the two tables of index.ts:64-75, empty."""

    def __init__(self):
        self.db = sqlite3.connect(":memory:", isolation_level=None)
        self.db.execute('CREATE TABLE "message" ("timestamp" TEXT, "userId" TEXT, '
                        '"content" BLOB, PRIMARY KEY ("timestamp", "userId"))')
        self.db.execute('CREATE TABLE "merkleTree" ("userId" TEXT PRIMARY KEY, '
                        '"merkleTree" TEXT)')

    def get_merkle_tree(self, user_id: str) -> dict:
        """`getMerkleTree` (index.ts:121-136): the stored tree, or the
        initial one for an owner never seen."""
        row = self.db.execute(
            'SELECT "merkleTree" FROM "merkleTree" WHERE "userId" = ?', (user_id,)).fetchone()
        return json.loads(row[0]) if row else {}

    def add_messages(self, user_id: str, messages, tree: dict) -> dict:
        """`addMessages` (index.ts:138-171), in one transaction;
        `messages` are (timestamp, content) in the request's order."""
        db = self.db
        parsed = [parse_timestamp(t) for t, _content in messages]
        hashes = timestamp_hashes(parsed)
        db.execute("BEGIN")
        try:
            for (timestamp, content), (millis, _c, _n), h in zip(messages, parsed, hashes):
                changes = db.execute(
                    'INSERT OR IGNORE INTO "message" ("timestamp", "userId", "content") '
                    'VALUES (?, ?, ?)', (timestamp, user_id, content)).rowcount
                if changes == 1:
                    tree_insert(tree, millis, h)
            db.execute('INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") '
                       'VALUES (?, ?)', (user_id, tree_to_string(tree)))
        except BaseException:
            db.execute("ROLLBACK")
            raise
        db.execute("COMMIT")
        return tree

    def get_messages(self, user_id: str, node_id: str, tree: dict, client_tree: dict) -> list:
        """`getMessages` (index.ts:173-202) → [(timestamp, content)]."""
        diff = tree_diff(tree, client_tree)
        if diff is None:
            return []
        since = render_timestamp(diff, 0, SYNC_NODE)
        return self.db.execute(
            'SELECT "timestamp", "content" FROM "message" WHERE "userId" = ? '
            'AND "timestamp" > ? AND "timestamp" NOT LIKE \'%\' || ? ORDER BY "timestamp"',
            (user_id, since, node_id)).fetchall()

    def sync(self, user_id: str, node_id: str, messages, client_tree: str) -> tuple:
        """`sync` (index.ts:204-216) → (messages, the stored tree's string)."""
        tree = self.add_messages(user_id, messages, self.get_merkle_tree(user_id))
        answer = self.get_messages(user_id, node_id, tree, json.loads(client_tree))
        return answer, tree_to_string(tree)

    def owner_dump(self, user_id: str) -> tuple:
        return owner_dump(lambda sql, args: self.db.execute(sql, args).fetchall(), user_id)

    def close(self) -> None:
        self.db.close()


def owner_dump(query, user_id: str) -> tuple:
    """Everything a relay holds of one owner, from any database
    `query(sql, args) -> rows` reads: its `message` rows in timestamp
    order and its `merkleTree` row."""
    messages = query('SELECT "timestamp", "userId", "content" FROM "message" '
                     'WHERE "userId" = ? ORDER BY "timestamp"', (user_id,))
    trees = query('SELECT "userId", "merkleTree" FROM "merkleTree" WHERE "userId" = ?',
                  (user_id,))
    return [tuple(r) for r in messages], [tuple(r) for r in trees]
