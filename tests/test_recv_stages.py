"""A client's `Receive`, tiled into named host stages (ISSUE 29):
`obs.anatomy.tiles` in `DbWorker.handle` and `anatomy.seam` in
`_receive` and the planners (`ops/winner_cache.py`, `ops/merge.py`), and
`recv_decrypt` around `decrypt_response_columns` on the caller's thread.

What is pinned: every `Receive` records each of `recv_clock`,
`recv_plan_host`, `recv_device_call`, `recv_pull`, `recv_apply`,
`recv_commit` and `recv_handle` once, on the worker's thread, on the
streamed route and on the cached one; the six never overlap and sum to
`recv_handle` within 5 %; the seven are posted in ONE acquisition of the
registry's lock; with annotations on each is one `evolu/<name>` profiler
annotation and with them off none is constructed; a Send, which runs
the same planner, records none of them; and none of it changes a byte
of the end state.

ISSUE 33 adds four children inside those tiles (`anatomy.part`):
`recv_tree_load` in `recv_clock`, `recv_tree_fold` in `recv_apply`,
`recv_tree_store` and `recv_tree_diff` in `recv_commit`. Each is observed
once a `Receive`, in the same `observe_many`, lies inside its parent,
and leaves the six tiles summing to `recv_handle`; the fold's counters
equal the batch's distinct minutes; and both new cells of the benchmark
rehearse `correct` with the new data files.

ISSUE 34 keeps the four spans where they are: load and diff time the
text comparison on a hit and the parse (and the walk) on a miss, the
fold its numpy decode and the fold per distinct node, the store the
dump and the `UPDATE`. Three counters say how often the new path
engages: `evolu_merkle_fold_nodes_total` and
`evolu_merkle_tree_text_checks_total` / `_hits_total{leg}`.

The stored winners a plan reads from SQLite are a part too,
`recv_winner_read` inside `recv_plan_host`: observed once in a `Receive`
that reads any (the streamed plans and the cache's seed), with no wait.

The tree is carried as its text (`MerkleText`): the load reads
`__clock` and parses nothing, the fold is one native pass from the old
text to the new (`evolu_merkle_fold_route_total{route=text}`, a fold a
`Receive`), the store writes that text, and the diff compares texts and
parses only where they differ.
"""

import itertools
import threading

import pytest

import evolu_tpu.utils.log as log_mod
from evolu_tpu.core.types import TableDefinition
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.runtime import messages as rmsg
from evolu_tpu.runtime.worker import DbWorker
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto
from evolu_tpu.utils.config import Config
from perf import gen_client, load_module

driver = load_module("drivers", "client")

pytestmark = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed receive needs both native libraries")

TILES = ("recv_clock", "recv_plan_host", "recv_device_call", "recv_pull",
         "recv_apply", "recv_commit")
ON_WORKER = TILES + ("recv_handle",)
# child of a tile -> the tile it lies inside
TREE = {"recv_tree_load": "recv_clock", "recv_tree_fold": "recv_apply",
        "recv_tree_store": "recv_commit", "recv_tree_diff": "recv_commit"}
# ... and every part: each Receive of the fixture reads stored winners
# (the two streamed plans, the seed of every cell, and the seed of the
# cells the fourth is the first to touch).
PARTS = {**TREE, "recv_winner_read": "recv_plan_host"}
NOW = 1_700_010_000_000


@pytest.fixture(scope="module")
def wires():
    # 4 x 1,000 messages over <= 250 cells: streamed, streamed, seeded, hit.
    messages = gen_client.build_messages(4000, 29, 50, 8)
    return gen_client.build_responses(messages, 4, gen_client.MNEMONIC)


class Device:
    """One restoring device: a fresh database and worker."""

    def __init__(self, now: int = NOW):
        self.outputs = []
        self.db = native.open_database(backend="native")
        self.worker = DbWorker(self.db, Config(backend="tpu"),
                               on_output=self.outputs.append,
                               now=itertools.count(now, 1000).__next__)
        self.worker.start(gen_client.MNEMONIC)
        self.worker.post(rmsg.UpdateDbSchema(tuple(
            TableDefinition.of(t, cols) for t, cols in gen_client.TABLES)))
        self.worker.flush()

    def receive(self, wire) -> None:
        packed, tree = native_crypto.decrypt_response_columns(wire, gen_client.MNEMONIC)
        self.worker.post(rmsg.Receive(packed, tree, None))
        self.worker.flush()

    def restore(self, wires) -> dict:
        for wire in wires:
            self.receive(wire)
        assert not [o for o in self.outputs if isinstance(o, rmsg.OnError)]
        return driver.dump(self.db)

    def close(self) -> None:
        self.worker.stop()
        self.db.close()


@pytest.fixture
def device(wires):
    Device().restore(wires)  # compile every program outside every reading
    dev = Device()
    yield dev
    dev.close()


def _hist(stage: str):
    h = metrics.registry.get_histogram("evolu_stage_ms", stage=stage)
    return (h[2], h[3]) if h else (0.0, 0)  # (sum of ms, count)


def test_each_stage_once_a_receive_and_the_six_tile_the_handle(device, wires):
    streamed0 = metrics.get_counter("evolu_winner_cache_streamed_cells_total")
    hits0 = metrics.get_counter("evolu_winner_cache_hits_total")
    for k, wire in enumerate(wires):
        before = {s: _hist(s) for s in ON_WORKER + ("recv_decrypt",)}
        rows0 = metrics.get_counter("evolu_stage_rows_total", stage="recv_decrypt")
        device.receive(wire)
        ms = {}
        for s, (sum0, count0) in before.items():
            sum1, count1 = _hist(s)
            assert count1 - count0 == 1, (k, s)
            ms[s] = sum1 - sum0
        assert metrics.get_counter("evolu_stage_rows_total", stage="recv_decrypt") \
            - rows0 == 1000
        tiled = sum(ms[s] for s in TILES)
        assert tiled <= ms["recv_handle"], (k, ms)  # no overlap
        assert tiled >= 0.95 * ms["recv_handle"], (k, ms)
        assert all(ms[s] > 0 for s in ON_WORKER)
    # Both plan routes were walked: winners streamed from SQLite, then from HBM.
    assert metrics.get_counter("evolu_winner_cache_streamed_cells_total") > streamed0
    assert metrics.get_counter("evolu_winner_cache_hits_total") > hits0


def test_one_registry_acquisition_a_receive(device, wires, monkeypatch):
    """The seven worker-thread stages and their waits (ISSUE 37) go to
    the registry in one `observe_many`; the caller's `recv_decrypt` in
    one more, its totals riding with it; no other write names a
    `recv_*` stage there. The tree's parts record no wait."""
    calls = []  # (writer, thread, [(family, stage named)])
    real = {w: getattr(metrics, w) for w in ("observe_many", "observe", "inc")}

    def spy_many(items, also_inc=()):
        items, also_inc = list(items), list(also_inc)
        named = [(f, labels.get("stage")) for f, _v, labels in items + also_inc]
        if any(str(stage).startswith("recv_") for _f, stage in named):
            calls.append(("observe_many", threading.get_ident(), named))
        real["observe_many"](items, also_inc=also_inc)

    def spy(writer):
        def write(name, *args, **labels):
            if str(labels.get("stage", "")).startswith("recv_"):
                calls.append((writer, threading.get_ident(), [(name, labels["stage"])]))
            real[writer](name, *args, **labels)
        return write

    monkeypatch.setattr(metrics, "observe_many", spy_many)
    monkeypatch.setattr(metrics, "observe", spy("observe"))
    monkeypatch.setattr(metrics, "inc", spy("inc"))
    for wire in wires:
        del calls[:]
        device.receive(wire)
        worker = device.worker._thread.ident
        on_worker = [c for c in calls if c[1] == worker]
        assert [c[0] for c in on_worker] == ["observe_many"], on_worker
        assert sorted(on_worker[0][2]) == sorted(
            [("evolu_stage_ms", s) for s in ON_WORKER + tuple(PARTS)]
            + [("evolu_stage_wait_ms", s) for s in ON_WORKER])
        (decrypt,) = [c for c in calls if c[1] != worker]
        assert decrypt[:2] == ("observe_many", threading.get_ident())
        assert decrypt[2] == [(f, "recv_decrypt") for f in (
            "evolu_stage_ms", "evolu_stage_wait_ms", "evolu_stage_seconds_total",
            "evolu_stage_rows_total", "evolu_stage_bytes_total")]


def test_each_tile_waits_no_longer_than_it_lasts(device, wires):
    """`evolu_stage_wait_ms{stage=recv_*}`: one observation a `Receive`
    beside each tile's `evolu_stage_ms`, wait <= the tile and (on this
    machine's nanosecond CPU clock) not under 0 by more than the two
    clocks' jitter; the tiles' waits add up to no more than the
    handle's, which is the thread's whole time off the CPU; a part has
    none."""
    def reading():
        return {s: (_hist(s), _wait(s)) for s in ON_WORKER + tuple(PARTS)}

    def _wait(stage):
        h = metrics.registry.get_histogram("evolu_stage_wait_ms", stage=stage)
        return (h[2], h[3]) if h else (0.0, 0)

    for wire in wires:
        before = reading()
        device.receive(wire)
        after = reading()
        ms, wait = {}, {}
        for s in ON_WORKER:
            assert after[s][1][1] - before[s][1][1] == 1, s
            ms[s] = after[s][0][0] - before[s][0][0]
            wait[s] = after[s][1][0] - before[s][1][0]
            assert -0.05 <= wait[s] <= ms[s], (s, wait[s], ms[s])
        # one clock reading a seam, so CPU time tiles as wall time does
        assert sum(wait[s] for s in TILES) <= wait["recv_handle"] + 0.05 * ms["recv_handle"]
        for child in PARTS:
            assert after[child][1] == (0.0, 0)


def test_every_stage_is_one_annotation_on_its_thread(device, wires):
    events, built = [], []

    class Recording:
        def __init__(self, name):
            built.append(name)
            self.name = name

        def __enter__(self):
            events.append(("open", self.name, threading.get_ident()))
            return self

        def __exit__(self, *exc):
            events.append(("close", self.name, threading.get_ident()))

    orig = log_mod._trace_annotation_cls
    log_mod._trace_annotation_cls = Recording
    try:
        for wire in wires:
            device.receive(wire)
    finally:
        log_mod._trace_annotation_cls = orig
    worker, caller = device.worker._thread.ident, threading.get_ident()
    by_name = {}
    for kind, name, tid in events:
        by_name.setdefault(name, {"open": [], "close": []})[kind].append(tid)
    for s in ON_WORKER:
        rec = by_name["evolu/" + s]
        assert rec["open"] == rec["close"] == [worker] * len(wires), s
    rec = by_name["evolu/recv_decrypt"]
    assert rec["open"] == rec["close"] == [caller] * len(wires)
    # The tiles in order inside the handle, one open at a time; the pull
    # wave lies inside recv_pull.
    order = [(kind, name[len("evolu/"):]) for kind, name, tid in events
             if tid == worker and name.startswith("evolu/")]
    # ... and the tree's parts inside their tiles (the fold twice: the
    # delta decode in the planner, the fold after the SQLite apply).
    inside = {"recv_clock": ["recv_tree_load"], "recv_pull": ["pull_wave"],
              "recv_plan_host": ["recv_winner_read"],
              "recv_apply": ["recv_tree_fold"] * 2,
              "recv_commit": ["recv_tree_store", "recv_tree_diff"]}
    one = [("open", "recv_handle")]
    for s in TILES:
        one += [("open", s)]
        for child in inside.get(s, []):
            one += [("open", child), ("close", child)]
        one += [("close", s)]
    one += [("close", "recv_handle")]
    assert order == one * len(wires)
    # Annotations off: the stand-in (or any class) is never constructed.
    count = len(built)
    device.receive(wires[0])
    assert len(built) == count


def test_a_send_runs_the_planner_and_records_no_recv_stage(device):
    before = {s: _hist(s)[1] for s in ON_WORKER}
    device.worker.post(rmsg.Send(tuple(
        rmsg.NewCrdtMessage("todo", f"row{i}", "title", f"mine {i}") for i in range(64))))
    device.worker.flush()
    assert not [o for o in device.outputs if isinstance(o, rmsg.OnError)]
    assert {s: _hist(s)[1] for s in ON_WORKER} == before
    anatomy.seam("plan_host")  # outside a tiled command: nothing, no error


def test_end_state_identical_with_metrics_disabled(wires):
    def restored(enabled: bool) -> dict:
        metrics.set_enabled(enabled)
        dev = Device()
        try:
            return dev.restore(wires)
        finally:
            dev.close()
            metrics.set_enabled(True)

    counts = {s: _hist(s)[1] for s in ON_WORKER}
    off = restored(False)
    assert {s: _hist(s)[1] for s in ON_WORKER} == counts  # nothing recorded
    on = restored(True)
    assert on == off and len(on["__message"]) == 4000
    assert {s: _hist(s)[1] - counts[s] for s in ON_WORKER} == dict.fromkeys(ON_WORKER, 4)


@pytest.mark.parametrize("child", sorted(PARTS))
def test_tree_part_once_a_receive_inside_its_tile(device, wires, child):
    parent = PARTS[child]
    for k, wire in enumerate(wires):
        before = {s: _hist(s) for s in ON_WORKER + (child,)}
        device.receive(wire)
        ms = {}
        for s, (sum0, count0) in before.items():
            sum1, count1 = _hist(s)
            assert count1 - count0 == 1, (k, s)
            ms[s] = sum1 - sum0
        assert 0 < ms[child] <= ms[parent], (k, ms)
        tiled = sum(ms[s] for s in TILES)  # the parts are inside, not beside
        assert 0.95 * ms["recv_handle"] <= tiled <= ms["recv_handle"], (k, ms)


def test_tree_parts_sum_inside_their_tile_and_are_no_ops_elsewhere(device, wires):
    before = {s: _hist(s) for s in tuple(TREE) + TILES}
    device.receive(wires[0])
    ms = {s: _hist(s)[0] - before[s][0] for s in before}
    assert ms["recv_tree_store"] + ms["recv_tree_diff"] <= ms["recv_commit"]
    # Outside a tiled command a part is one shared no-op context, and a
    # Send (read_clock, the fold, update_clock) observes none.
    assert anatomy.part("tree_fold") is anatomy.part("tree_load")
    with anatomy.part("tree_fold"):
        pass
    counts = {s: _hist(s)[1] for s in TREE}
    device.worker.post(rmsg.Send((rmsg.NewCrdtMessage("todo", "row1", "title", "mine"),)))
    device.worker.flush()
    assert {s: _hist(s)[1] for s in TREE} == counts


@pytest.mark.parametrize("shape", ["one-minute", "months"])
def test_fold_counters_equal_the_batches_distinct_minutes(shape):
    from perf import gen_history

    messages = (gen_client.build_messages(4000, 33, 50, 8) if shape == "one-minute" else
                gen_history.build_messages(4000, 33, 50, 8, 30, 3, 30))
    wires = gen_client.build_responses(messages, 4, gen_client.MNEMONIC)
    dev = Device(now=NOW + 366 * 86_400_000)  # after the months' last message
    try:
        for wire, batch in zip(wires, gen_client.split_responses(messages, 4)):
            read = {name: metrics.get_counter(name) for name in (
                "evolu_merkle_fold_minutes_total", "evolu_merkle_fold_calls_total")}
            legs = {leg: metrics.get_counter("evolu_merkle_tree_bytes_total", leg=leg)
                    for leg in ("load", "store", "remote")}
            loaded = dev.db.exec_sql_query('SELECT "merkleTree" FROM "__clock"')[0]["merkleTree"]
            dev.receive(wire)
            stored = dev.db.exec_sql_query('SELECT "merkleTree" FROM "__clock"')[0]["merkleTree"]
            assert metrics.get_counter("evolu_merkle_fold_calls_total") \
                - read["evolu_merkle_fold_calls_total"] == 1
            assert metrics.get_counter("evolu_merkle_fold_minutes_total") \
                - read["evolu_merkle_fold_minutes_total"] \
                == len({m.timestamp[:16] for m in batch})
            # The relay's tree in the response is the client's after it.
            want = {"load": len(loaded), "store": len(stored), "remote": len(stored)}
            assert {leg: metrics.get_counter("evolu_merkle_tree_bytes_total", leg=leg)
                    - legs[leg] for leg in legs} == want
        assert not [o for o in dev.outputs if isinstance(o, rmsg.OnError)]
        assert (len(stored) < 1000) == (shape == "one-minute")
    finally:
        dev.close()


@pytest.mark.parametrize("shape", ["one-minute", "months"])
def test_tree_counters_say_how_often_the_new_path_engages(shape):
    from evolu_tpu.core import merkle
    from evolu_tpu.core.timestamp import timestamp_from_string
    from perf import gen_history

    messages = (gen_client.build_messages(4000, 34, 50, 8) if shape == "one-minute" else
                gen_history.build_messages(4000, 34, 50, 8, 30, 3, 30))
    wires = gen_client.build_responses(messages, 4, gen_client.MNEMONIC)
    text = {(kind, leg): lambda kind=kind, leg=leg: metrics.get_counter(
        f"evolu_merkle_tree_text_{kind}_total", leg=leg)
        for kind in ("checks", "hits") for leg in ("load", "remote")}
    text[("route", "text")] = lambda: metrics.get_counter(
        "evolu_merkle_fold_route_total", route="text")
    dev = Device(now=NOW + 366 * 86_400_000)
    try:
        for k, (wire, batch) in enumerate(zip(wires, gen_client.split_responses(messages, 4))):
            nodes0 = metrics.get_counter("evolu_merkle_fold_nodes_total")
            text0 = {key: read() for key, read in text.items()}
            dev.receive(wire)
            keys = {merkle.minutes_base3(timestamp_from_string(m.timestamp).millis)
                    for m in batch}
            # Each distinct node once, written by the text fold.
            want = 1 + len({key[:i] for key in keys for i in range(1, 17)})
            assert want < 17 * len(keys) / 4 or len(keys) == 1
            assert metrics.get_counter("evolu_merkle_fold_nodes_total") - nodes0 == want
            # The load compares nothing (it parses nothing); every Receive
            # compares its text with the relay's, and folds as text.
            assert {key: read() - text0[key] for key, read in text.items()} == {
                ("checks", "load"): 0, ("hits", "load"): 0,
                ("checks", "remote"): 1, ("hits", "remote"): 1, ("route", "text"): 1}
        assert not [o for o in dev.outputs if isinstance(o, rmsg.OnError)]
    finally:
        dev.close()


def test_load_and_diff_compare_on_a_hit_and_parse_inside_their_span_on_a_miss(
        device, wires, monkeypatch):
    """The four spans keep their extents: nothing in `recv_tree_load` or
    `recv_tree_store` parses or dumps the tree (the text is read and
    written as it is), and what is inside `recv_tree_diff` is the
    comparison, or the parse of both trees (and the walk) where it
    misses."""
    import json

    import evolu_tpu.runtime.worker as worker_mod

    events = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            if self.name.startswith("evolu/recv_tree_"):
                events.append("<" + self.name[len("evolu/recv_tree_"):])
            return self

        def __exit__(self, *exc):
            if self.name.startswith("evolu/recv_tree_"):
                events.append(self.name[len("evolu/recv_tree_"):] + ">")

    def spy(module, name, label):
        real = getattr(module, name)
        monkeypatch.setattr(module, name, lambda *a: events.append(label) or real(*a))

    spy(json, "loads", "parse")
    spy(json, "dumps", "dump")
    spy(worker_mod, "merkle_tree_from_string", "parse-relay")
    spy(worker_mod, "diff_merkle_trees", "walk")
    monkeypatch.setattr(log_mod, "_trace_annotation_cls", Recording)
    hit = ["<load", "load>", "<fold", "fold>", "<fold", "fold>",
           "<store", "store>", "<diff", "diff>"]
    device.receive(wires[0])
    assert events == hit
    del events[:]
    device.receive(wires[1])
    assert events == hit
    # A relay that is ahead (no messages, the tree of the next response):
    # the texts differ, so the relay's is parsed and walked, inside the span.
    _packed, ahead = native_crypto.decrypt_response_columns(wires[2], gen_client.MNEMONIC)
    del events[:]
    device.worker.post(rmsg.Receive((), ahead, None))
    device.worker.flush()
    assert events == ["<load", "load>", "<diff", "parse-relay", "parse", "walk", "parse",
                      "diff>"]
    assert not [o for o in device.outputs if isinstance(o, rmsg.OnError)]


@pytest.mark.parametrize("cell", ["client-todo-months.restore", "client-todo.restore"])
def test_rehearsal_of_the_client_cells_ends_correct_with_the_tree_metrics(cell):
    """`perf/run.py --rehearse --trace 1` (which runs `perf/selfcheck.py`
    first) on the CPU: control flow, counts and `correct`, no device
    number. Both client cells print the tree metrics; the new one also
    its own `recv_handle_ms` and `window_compiles`."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    done = subprocess.run(
        [sys.executable, os.path.join(root, "perf", "run.py"), "--workload", cell,
         "--seed", str(2**31 + 33), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    got = line["metrics"]
    tree = {"recv_tree_load_ms", "recv_tree_fold_ms", "recv_tree_store_ms",
            "recv_tree_diff_ms", "tree_minutes_receive", "tree_kb_receive",
            "tree_nodes_receive", "tree_text_hit_share", "tree_text_fold_share"}
    assert tree <= set(got)
    assert got["tree_text_hit_share"]["value"] == 100.0  # the relay's text is the client's
    assert got["tree_text_fold_share"]["value"] == 100.0  # every fold text to text
    assert got["winner_read_native_share"]["value"] == 100.0  # no temp-table join
    assert got["recv_winner_read_ms"]["value"] > 0
    if cell == "client-todo-months.restore":
        assert {"recv_handle_ms.months", "window_compiles.months"} <= set(got)
        assert got["window_compiles.months"]["value"] == 0
        assert got["tree_minutes_receive"]["value"] == 30 * 3 * 30 / 4  # 675 a response
        parts = sum(got[f"recv_tree_{p}_ms"]["value"] for p in ("load", "fold", "store", "diff"))
        assert parts < got["recv_handle_ms.months"]["value"]
    else:
        assert "recv_handle_ms.months" not in got
        assert got["tree_minutes_receive"]["value"] == 1
        assert got["tree_nodes_receive"]["value"] == 17


def test_perf_selfcheck_reads_every_client_layer_file():
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, os.path.join(root, "perf", "selfcheck.py")],
                          capture_output=True, text=True, timeout=300, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    listed = set(os.listdir(os.path.join(root, "perf", "layers")))
    assert {f"{s}_ms.json" for s in ON_WORKER} | {
        "decrypt_us_msg.json", "winner_cache_hit_share.json",
        "window_compiles.client.json"} <= listed
    assert {f"{s}_ms.json" for s in TREE} | {
        "tree_minutes_receive.json", "tree_kb_receive.json",
        "tree_nodes_receive.json", "tree_text_hit_share.json", "tree_text_fold_share.json",
        "recv_handle_ms.months.json", "window_compiles.months.json"} <= listed
