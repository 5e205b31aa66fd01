"""The plain reference of `client-todo`: the reference client's
`receive.ts` and `applyMessages.ts:26-131`, one message at a time over
stdlib `sqlite3`.

For every message: fold its timestamp into the clock
(`receiveTimestamp`), select the cell's newest stored timestamp, upsert
the app table if the message is newer, insert it into `__message`
unless that timestamp is the cell's winner already, and XOR its hash
into the Merkle tree. Nothing here is the program's: the timestamp's
string form, the HLC rule (`timestamp.ts:125-165`), the tree's insert
and string form (`merkleTree.ts:31-50`, `types.ts:80-81`) are written
out below, and the hash is the benchmark's own numpy murmur3
(`perf/gen.py`, which `perf/selfcheck.py` holds to the reference's
golden value). The relay's tree inside each response is folded by the
same `tree_insert` (`perf/gen_client.py`), so a hash or a minute key
that `evolu_tpu.core` got wrong would end no sync and equal no dump.
"""

import calendar
import copy
import hashlib
import json
import sqlite3
import time

from perf.gen import _ascii_rows, murmur3_32_rows

MAX_DRIFT = 60_000  # config.ts:9
MAX_COUNTER = 65_535  # timestamp.ts:100


def parse_timestamp(s: str) -> tuple:
    """`2023-11-14T22:13:20.000Z-0000-0123456789abcdef` → (millis,
    counter, node) (timestamp.ts:50-58)."""
    assert len(s) == 46 and s[23:25] == "Z-" and s[29] == "-", s
    seconds = calendar.timegm(time.strptime(s[:19], "%Y-%m-%dT%H:%M:%S"))
    return seconds * 1000 + int(s[20:23]), int(s[25:29], 16), s[30:]


def render_timestamp(millis: int, counter: int, node: str) -> str:
    """timestamp.ts:43-48: ISO millis, four upper-case hex digits, the node."""
    iso = time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(millis // 1000))
    return f"{iso}.{millis % 1000:03d}Z-{counter:04X}-{node}"


def receive_clock(local: tuple, remote: tuple, now: int) -> tuple:
    """`receiveTimestamp` (timestamp.ts:125-165) on (millis, counter, node)."""
    millis = max(local[0], remote[0], now)
    if millis - now > MAX_DRIFT:
        raise ValueError(f"timestamp drift: {millis} against {now}")
    if local[2] == remote[2]:
        raise ValueError(f"duplicate node {local[2]}")
    if millis == local[0] and millis == remote[0]:
        counter = max(local[1], remote[1]) + 1
    elif millis == local[0]:
        counter = local[1] + 1
    elif millis == remote[0]:
        counter = remote[1] + 1
    else:
        counter = 0
    if counter > MAX_COUNTER:
        raise ValueError("timestamp counter overflow")
    return millis, counter, local[2]


def timestamp_hashes(parsed) -> list:
    """murmur3 of each timestamp's canonical string (timestamp.ts:87-88)."""
    if not parsed:
        return []
    return murmur3_32_rows(_ascii_rows([render_timestamp(*t) for t in parsed], 46)).tolist()


def _int32(x: int) -> int:
    """What JS's `^` leaves: a signed 32-bit number."""
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x >= 1 << 31 else x


def tree_insert(tree: dict, millis: int, h: int) -> None:
    """`insertIntoMerkleTree` (merkleTree.ts:31-50), in place: XOR the
    hash into the root and into every node on the path of the minute's
    base-3 digits."""
    minutes, key = millis // 60_000, ""
    while minutes:
        minutes, digit = divmod(minutes, 3)
        key = str(digit) + key
    node = tree
    node["hash"] = _int32(node.get("hash", 0) ^ h)
    for c in key or "0":
        node = node.setdefault(c, {})
        node["hash"] = _int32(node.get("hash", 0) ^ h)


def tree_to_string(tree: dict) -> str:
    """`JSON.stringify` of the tree (types.ts:80-81): JS orders the
    digit keys before `hash`, and writes no space."""
    def ordered(node):
        out = {c: ordered(node[c]) for c in "012" if c in node}
        if "hash" in node:
            out["hash"] = node["hash"]
        return out

    return json.dumps(ordered(tree), separators=(",", ":"))


def _quote(name: str) -> str:
    return '"' + name.replace('"', '""') + '"'


class ReferenceClient:
    """One device: an empty database with the owner's row, the schema's
    tables, a clock at the initial timestamp and an empty tree."""

    def __init__(self, tables, mnemonic: str, node: str = "0" * 16):
        self.tables = tuple(tables)
        self.db = sqlite3.connect(":memory:", isolation_level=None)
        self.clock = (0, 0, node)
        self.tree = {}
        db = self.db
        # initDbModel.ts:29-81
        db.execute('CREATE TABLE __message ("timestamp" BLOB PRIMARY KEY, "table" BLOB, '
                   '"row" BLOB, "column" BLOB, "value" BLOB)')
        db.execute('CREATE INDEX index__message ON __message '
                   '("table", "row", "column", "timestamp")')
        db.execute('CREATE TABLE __clock ("timestamp" BLOB, "merkleTree" BLOB)')
        db.execute('INSERT INTO __clock VALUES (?, ?)',
                   (render_timestamp(*self.clock), tree_to_string(self.tree)))
        db.execute('CREATE TABLE __owner ("id" BLOB, "mnemonic" BLOB)')
        db.execute('INSERT INTO __owner VALUES (?, ?)',
                   (hashlib.sha256(mnemonic.encode("utf-8")).hexdigest()[:21], mnemonic))
        # updateDbSchema.ts:85-103
        for table, columns in self.tables:
            cols = ", ".join(f"{_quote(c)} BLOB" for c in columns)
            db.execute(f'CREATE TABLE {_quote(table)} ("id" TEXT PRIMARY KEY, {cols})')

    def receive(self, messages, now: int) -> None:
        """One `Receive` in one transaction: `messages` are
        (timestamp, table, row, column, value) in the response's order,
        `now` the device's wall clock for the command."""
        db = self.db
        db.execute("BEGIN")
        try:
            clock, tree = self.clock, copy.deepcopy(self.tree)
            parsed = [parse_timestamp(m[0]) for m in messages]
            for remote in parsed:
                clock = receive_clock(clock, remote, now)
            hashes = timestamp_hashes(parsed)
            for (timestamp, table, row, column, value), remote, h in zip(
                    messages, parsed, hashes):
                winner = db.execute(
                    'SELECT "timestamp" FROM "__message" WHERE "table" = ? AND "row" = ? '
                    'AND "column" = ? ORDER BY "timestamp" DESC LIMIT 1',
                    (table, row, column)).fetchone()
                if winner is None or winner[0] < timestamp:
                    db.execute(
                        f'INSERT INTO {_quote(table)} ("id", {_quote(column)}) VALUES (?, ?) '
                        f'ON CONFLICT("id") DO UPDATE SET {_quote(column)} = ?',
                        (row, value, value))
                if winner is None or winner[0] != timestamp:
                    db.execute(
                        'INSERT INTO "__message" ("timestamp", "table", "row", "column", '
                        '"value") VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING',
                        (timestamp, table, row, column, value))
                    tree_insert(tree, remote[0], h)
            db.execute('UPDATE "__clock" SET "timestamp" = ?, "merkleTree" = ?',
                       (render_timestamp(*clock), tree_to_string(tree)))
        except BaseException:
            db.execute("ROLLBACK")
            raise
        db.execute("COMMIT")
        self.clock, self.tree = clock, tree

    def dump(self) -> dict:
        return dump(lambda sql: self.db.execute(sql).fetchall(), self.tables)

    def close(self) -> None:
        self.db.close()


def dump(query, tables) -> dict:
    """Everything a restore leaves behind, in an order of its own, from
    any database `query(sql) -> rows` reads. The clock's node is drawn
    at random by each device, so the clock compares as millis and
    counter (the first 29 characters) and tree; the rest byte for byte."""
    state = {
        "__message": query('SELECT "timestamp", "table", "row", "column", "value" '
                           'FROM "__message" ORDER BY "timestamp"'),
        "__clock": [(t[:29], tree) for t, tree in
                    query('SELECT "timestamp", "merkleTree" FROM "__clock"')],
        "__owner": query('SELECT "id", "mnemonic" FROM "__owner"'),
    }
    for table, columns in tables:
        cols = ", ".join(_quote(c) for c in ("id", *columns))
        state[table] = query(f'SELECT {cols} FROM {_quote(table)} ORDER BY "id"')
    return {k: [tuple(r) for r in rows] for k, rows in state.items()}
