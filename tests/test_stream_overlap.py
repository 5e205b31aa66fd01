"""`BatchReconciler.reconcile_stream` stages pass k+1 on the engine's one
helper thread while the caller's thread lands pass k (ISSUE 39).

What is pinned: a stream of 1, 2 and 5 passes over a native sharded
store ends byte-identical (rows, stored trees, answers) to sequential
`reconcile` calls on a twin and to the per-request replay on the python
backend, with rows repeated inside a request and across passes (the
device hashed them optimistically; the landing recomputes); the first
pass is staged on the caller's thread and every later one on a thread
named `evolu-stage`, let go only once the landing has entered its native
insert; every shard-set call runs on the caller's thread; a stream of one
pass and a route other than `stream` start no thread; the stream is
iterated on the caller's thread, never more than one pass ahead; a
staging that raises surfaces after the pass before it has landed and
answered; a landing that raises propagates ITS exception, leaves no shard
inside a transaction and the helper idle; `close()` joins the helper and
may be called twice; the counter and the join stage the mechanism brings.
"""

import threading
import zlib

import pytest

from conftest import relay_store_dump
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import ledger as ledger_mod
from evolu_tpu.obs import metrics
from evolu_tpu.server import engine as engine_mod
from evolu_tpu.server.engine import BatchReconciler
from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
from evolu_tpu.storage import native
from evolu_tpu.sync import protocol

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native host library unavailable")

BASE = 1_700_000_000_000
SHARDS = 8
OWNERS = [f"owner-{i}" for i in range(12)]  # spread over the shards by crc32
ROWS = 6  # new rows an owner a pass


def _ts(owner: str, i: int) -> str:
    node = f"{zlib.crc32(owner.encode()):016x}"
    return timestamp_to_string(Timestamp(BASE + i * 17_000, i % 3, node))


def _request(owner, indices):
    msgs = tuple(
        protocol.EncryptedCrdtMessage(_ts(owner, i), b"ct:%d:" % i + owner.encode())
        for i in indices)
    return protocol.SyncRequest(msgs, owner, "f" * 16, "{}")


def _passes(n):
    """n passes, one request an owner a pass (so the sequential server
    answers each request as the batched pass does). Every pass but the
    first re-sends the last three rows of the pass before for every
    third owner, and the first owner repeats two rows inside its own
    request; the last pass of a longer stream also carries an owner
    whose rows are ALL stored already."""
    passes = []
    for k in range(n):
        reqs = []
        for j, owner in enumerate(OWNERS):
            rows = list(range(k * ROWS, (k + 1) * ROWS))
            if k and j % 3 == 0:
                rows = list(range(k * ROWS - 3, k * ROWS)) + rows
            if j == 0:
                rows += rows[:2]
            reqs.append(_request(owner, rows))
        if k and k == n - 1:
            reqs[-1] = _request(OWNERS[-1], range(ROWS))
        passes.append(reqs)
    return passes


def _engine():
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    return store, BatchReconciler(store)


def _replay(passes):
    """→ (python store, its answers pass by pass): the sequential server."""
    oracle = RelayStore(":memory:", "python")
    with ledger_mod.quarantine():
        return oracle, [[oracle.sync(r) for r in reqs] for reqs in passes]


def _assert_equals_replay(store, oracle):
    dump = relay_store_dump(store)
    assert sorted(r for msgs, _t in dump for r in msgs) == oracle.db.exec(
        'SELECT * FROM "message" ORDER BY "timestamp", "userId"')
    assert sorted(r for _m, ts in dump for r in ts) == oracle.db.exec(
        'SELECT * FROM "merkleTree" ORDER BY "userId"')


def _stored_rows(store):
    return sum(s.db.exec('SELECT COUNT(*) FROM "message"')[0][0] for s in store.shards)


def _assert_no_open_transaction(store):
    """Python's flag AND SQLite's own state: a handle inside a
    transaction refuses BEGIN."""
    for i, s in enumerate(store.shards):
        assert not s.db._in_txn, f"shard {i} still flagged inside a transaction"
        s.db.begin()
        s.db.rollback()


def _stage_threads():
    return [t for t in threading.enumerate() if t.name.startswith("evolu-stage")]


def _staged():
    return {t: metrics.get_counter("evolu_engine_stream_staged_total", thread=t)
            for t in ("caller", "helper")}


def _joins():
    h = metrics.registry.get_histogram("evolu_stage_ms", stage="pass_stage_join")
    return h[3] if h else 0


class _Tape:
    """What ran where, in order: `start_batch` and the two shard-set
    calls wrapped to record (what, thread name, thread id) as they are
    entered, and the insert's `entering` as it fires."""

    def __init__(self, monkeypatch, eng):
        self.events = []
        self._lock = threading.Lock()
        real_start = eng.start_batch
        real_insert = engine_mod.relay_insert_packed_shards
        real_commit = engine_mod.relay_commit_shards

        def start_batch(requests):
            self.note("start_batch")
            return real_start(requests)

        def insert(dbs, batches, also_count=(), entering=None):
            self.note("insert")

            def fired():
                self.note("entering")
                if entering is not None:
                    entering()
            return real_insert(dbs, batches, also_count, fired)

        def commit(dbs, tree_rows):
            self.note("commit")
            return real_commit(dbs, tree_rows)

        monkeypatch.setattr(eng, "start_batch", start_batch)
        monkeypatch.setattr(engine_mod, "relay_insert_packed_shards", insert)
        monkeypatch.setattr(engine_mod, "relay_commit_shards", commit)

    def note(self, what):
        t = threading.current_thread()
        with self._lock:
            self.events.append((what, t.name, t.ident))

    def of(self, what):
        return [e for e in self.events if e[0] == what]


@pytest.mark.parametrize("n", (1, 2, 5))
def test_stream_ends_as_sequential_reconcile_and_the_python_replay(n):
    passes = _passes(n)
    oracle, want = _replay(passes)
    seq_store, seq_eng = _engine()
    store, eng = _engine()
    try:
        with ledger_mod.quarantine():
            seq = [seq_eng.reconcile(reqs) for reqs in passes]
            got = eng.reconcile_stream(passes)
        assert got == want and seq == want
        assert relay_store_dump(store) == relay_store_dump(seq_store)
        assert store.owner_trees() == seq_store.owner_trees()
        _assert_equals_replay(store, oracle)
        assert _stored_rows(store) == len(
            {(m.timestamp, r.user_id) for reqs in passes for r in reqs for m in r.messages})
    finally:
        eng.close(), seq_eng.close(), store.close(), seq_store.close(), oracle.close()


@pytest.mark.parametrize("n", (2, 5))
def test_later_passes_are_staged_on_the_helper_and_sqlite_stays_on_the_caller(monkeypatch, n):
    store, eng = _engine()
    tape = _Tape(monkeypatch, eng)
    me = threading.current_thread()
    try:
        with ledger_mod.quarantine():
            eng.reconcile_stream(_passes(n))
        starts = tape.of("start_batch")
        assert len(starts) == n
        assert starts[0][1:] == (me.name, me.ident)
        assert all(name.startswith("evolu-stage") for _w, name, _i in starts[1:])
        assert len({ident for _w, _n, ident in starts[1:]}) == 1, "ONE helper thread"
        for what in ("insert", "entering", "commit"):
            calls = tape.of(what)
            assert len(calls) == n, what
            assert all(ident == me.ident for _w, _n, ident in calls), what
        # The helper is let go by the landing's `entering`, not before:
        # pass k+1's staging starts after pass k's insert has its
        # arguments built and is about to drop the interpreter lock.
        order = [what for what, _n, _i in tape.events if what in ("entering", "start_batch")]
        assert order == ["start_batch"] + ["entering", "start_batch"] * (n - 1) + ["entering"]
    finally:
        eng.close(), store.close()


@pytest.mark.parametrize("case", ("one_pass", "generic_route", "reconcile"))
def test_no_thread_where_there_is_nothing_to_overlap(case):
    before = set(_stage_threads())
    if case == "generic_route":
        store = ShardedRelayStore(":memory:", "python", shards=2)
        eng = BatchReconciler(store)
        assert eng._route(live=False) == "generic"
    else:
        store, eng = _engine()
    try:
        with ledger_mod.quarantine():
            if case == "reconcile":
                for reqs in _passes(3):
                    eng.reconcile(reqs)
            else:
                eng.reconcile_stream(_passes(1 if case == "one_pass" else 3))
        assert eng._stage_pool is None
        assert set(_stage_threads()) == before
    finally:
        eng.close(), store.close()


def test_a_generator_is_consumed_on_the_callers_thread_one_pass_ahead(monkeypatch):
    store, eng = _engine()
    tape = _Tape(monkeypatch, eng)
    me = threading.get_ident()
    passes = _passes(4)
    pulled = []

    def stream():
        for k, reqs in enumerate(passes):
            # (thread, passes landed when pass k is asked for)
            pulled.append((threading.get_ident(), len(tape.of("commit"))))
            yield reqs

    try:
        with ledger_mod.quarantine():
            got = eng.reconcile_stream(stream())
        assert len(got) == 4
        assert [ident for ident, _c in pulled] == [me] * 4
        # pass k is asked for once pass k-2 has landed: one staged ahead
        assert [landed for _i, landed in pulled] == [0, 0, 1, 2]
    finally:
        eng.close(), store.close()


def test_an_empty_pass_in_the_stream_does_not_park_the_helper():
    """A pass without a message has no live shard: its landing never
    reaches the insert, so nothing there lets the helper go."""
    passes = _passes(3)
    passes[1] = [protocol.SyncRequest((), o, "f" * 16, "{}") for o in OWNERS[:3]]
    oracle, want = _replay(passes)
    store, eng = _engine()
    try:
        with ledger_mod.quarantine():
            assert eng.reconcile_stream(passes) == want
        _assert_equals_replay(store, oracle)
    finally:
        eng.close(), store.close(), oracle.close()


def test_a_staging_that_raises_surfaces_after_the_pass_before_it_has_landed(monkeypatch):
    passes = _passes(2)
    bad = [protocol.SyncRequest(
        (protocol.EncryptedCrdtMessage("not-46-chars", b"c"),), "uB", "f" * 16, "{}")]
    never = _passes(3)[2]
    oracle, want = _replay(passes)
    store, eng = _engine()
    produced, asked = [], []
    real_finish = eng.finish_batch

    def finish_batch(st, **kw):
        produced.append(real_finish(st, **kw))
        return produced[-1]

    def stream():
        for k, reqs in enumerate(passes + [bad, never]):
            asked.append(k)
            yield reqs

    monkeypatch.setattr(eng, "finish_batch", finish_batch)
    try:
        with ledger_mod.quarantine(), pytest.raises(ValueError):
            eng.reconcile_stream(stream())
        assert asked == [0, 1, 2], "the pass behind the bad one is never asked for"
        assert produced == want, "both good passes answered before the staging's exception"
        _assert_equals_replay(store, oracle)
        _assert_no_open_transaction(store)
        # The helper survived its task's exception and stages again.
        with ledger_mod.quarantine():
            assert len(eng.reconcile_stream([never, _passes(4)[3]])) == 2
        assert len(_stage_threads()) >= 1
    finally:
        eng.close(), store.close(), oracle.close()


@pytest.mark.parametrize("staging", ("good", "bad"))
def test_a_landing_that_raises_propagates_its_own_exception_and_leaves_all_clean(
        monkeypatch, staging):
    passes = _passes(3)
    if staging == "bad":
        passes[1] = [protocol.SyncRequest(
            (protocol.EncryptedCrdtMessage("not-46-chars", b"c"),), "uB", "f" * 16, "{}")]
    store, eng = _engine()
    staged_done = threading.Event()
    real_start, real_commit = eng.start_batch, engine_mod.relay_commit_shards
    starts = []

    def start_batch(requests):
        starts.append(threading.current_thread().name)
        try:
            return real_start(requests)
        finally:
            if len(starts) == 2:
                staged_done.set()

    def commit(dbs, tree_rows):
        raise RuntimeError("the landing's own failure")

    monkeypatch.setattr(eng, "start_batch", start_batch)
    monkeypatch.setattr(engine_mod, "relay_commit_shards", commit)
    try:
        with ledger_mod.quarantine(), pytest.raises(RuntimeError, match="landing's own"):
            eng.reconcile_stream(passes)
        # The stream waited for the helper before it let the exception out.
        assert staged_done.is_set() and len(starts) == 2
        assert starts[1].startswith("evolu-stage")
        _assert_no_open_transaction(store)
        assert _stored_rows(store) == 0, "the failed pass rolled back on every shard"
        # The helper is idle: a task handed to it runs at once.
        assert eng._stage_pool.submit(lambda: 7).result(timeout=30) == 7
        # ... and the engine still works, streamed and not.
        monkeypatch.setattr(engine_mod, "relay_commit_shards", real_commit)
        good = _passes(3)
        oracle, want = _replay(good)
        with ledger_mod.quarantine():
            assert eng.reconcile(good[0]) == want[0]
            assert eng.reconcile_stream(good[1:]) == want[1:]
        _assert_equals_replay(store, oracle)
        oracle.close()
    finally:
        eng.close(), store.close()


def test_close_joins_the_helper_and_may_be_called_twice():
    store, eng = _engine()
    try:
        with ledger_mod.quarantine():
            eng.reconcile_stream(_passes(2))
        helpers = [t for t in _stage_threads() if t.is_alive()]
        assert eng._stage_pool is not None and helpers
        eng.close()
        assert eng._stage_pool is None and eng._pull_pool is None
        for t in helpers:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in helpers)
        eng.close()
        # A closed engine makes its threads again when it is used again.
        with ledger_mod.quarantine():
            assert len(eng.reconcile_stream(_passes(4)[2:])) == 2
    finally:
        eng.close(), store.close()


@pytest.mark.parametrize("n", (1, 2, 5))
def test_the_counter_reads_one_caller_and_the_rest_helper_and_the_join_is_observed(n):
    store, eng = _engine()
    staged0, joins0 = _staged(), _joins()
    try:
        with ledger_mod.quarantine():
            eng.reconcile_stream(_passes(n))
        staged = _staged()
        assert staged["caller"] - staged0["caller"] == 1
        assert staged["helper"] - staged0["helper"] == n - 1
        assert _joins() - joins0 == n - 1
        # the join reads no CPU clock: nothing of it in the wait family
        assert metrics.registry.get_histogram(
            "evolu_stage_wait_ms", stage="pass_stage_join") is None
    finally:
        eng.close(), store.close()


def test_the_inserts_entering_fires_once_on_the_callers_thread_with_the_locks_held():
    """`relay_insert_packed_shards(entering=...)`: the last Python before
    the native call; not reached where the call's own checks raise."""
    store, eng = _engine()
    st = eng.start_batch(_passes(1)[0])
    dbs = [store.shards[si].db for si in st["live"]]
    batches = [st["shard_data"][si] for si in st["live"]]
    seen = []

    def entering():
        seen.append((threading.get_ident(), [db._in_txn for db in dbs]))

    try:
        with pytest.raises(native.UnknownError):
            short = (batches[0][0], batches[0][1], batches[0][2][:-46], *batches[0][3:])
            native.relay_insert_packed_shards(dbs, [short] + batches[1:], entering=entering)
        assert seen == []
        flags, _stored = native.relay_insert_packed_shards(dbs, batches, entering=entering)
        assert seen == [(threading.get_ident(), [False] * len(dbs))]
        assert all(f.all() for f in flags) and all(db._in_txn for db in dbs)
        native.relay_commit_shards(dbs, [[] for _ in dbs])
    finally:
        eng.close(), store.close()
