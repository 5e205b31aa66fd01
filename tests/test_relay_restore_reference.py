"""`relay-restore` against its plain reference at a small size.

A device restored from its mnemonic pulls its owner's whole history from a
served `RelayServer` in one answer, through `DbWorker` and a real
`SyncTransport` (the cell's driver, `perf/drivers/relay_restore.py`), and
must end byte-equal to `perf/reference/cold_sync.py`. Here too: the cut
that upstream's one `now` a command forces (65,535 messages older than the
clock fold, 65,536 overflow), the `sync_post` stage the sync client
records once a round, the cell rehearsed, and its data files.
"""

import json
import os
import subprocess
import sys
import tempfile
import urllib.error

import numpy as np
import pytest

from evolu_tpu.core.timestamp import receive_timestamps_batch_packed, timestamp_to_string
from evolu_tpu.core.types import (CrdtMessage, Owner, Timestamp,
                                  TimestampCounterOverflowError)
from evolu_tpu.obs import metrics
from evolu_tpu.runtime.messages import SyncRequestInput
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto, protocol
from evolu_tpu.sync.client import SyncTransport, encrypt_messages
from evolu_tpu.utils.config import Config
from perf import gen, gen_history, load_module, readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "relay-restore.cold-sync"
driver = load_module("drivers", "relay_restore")
cold_sync = load_module("reference", "cold_sync")
client_reference = load_module("reference", "client_todo")

NEW_METRICS = ("sync_post_ms", "restore_p50_s")
TWINS = {"respond_fetch_ms.restore": "respond_fetch_ms",
         "pass_respond_ms.restore": "pass_respond_ms",
         "req_respond_ms.restore": "req_respond_ms",
         "recv_handle_ms.restore": "recv_handle_ms",
         "decrypt_us_msg.restore": "decrypt_us_msg",
         "window_compiles.restore": "window_compiles.months"}

needs_native = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the served relay's packed ingest and the fused decrypt need both native libraries")


def _config() -> dict:
    with open(os.path.join(ROOT, "perf", "configs", "relay-restore.json")) as f:
        return json.load(f)


# (a) the restore through the product's path, against the plain reference


@needs_native
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_restored_device_equals_the_cold_sync_reference(backend):
    cfg = {**_config(), **_config()["rehearsal"], "backend": backend,
           "history": 600, "messages": 800, "owners": 8, "preload_batches": 2}
    with tempfile.TemporaryDirectory() as scratch:
        state = driver.setup(cfg, 2**31 + 46, scratch)
        try:
            records = [state["restore"]() for _ in range(2)]
            for record in records:
                assert record["error"] is None, record["error"]
                assert record["received"] == [600] and len(record["posts"]) == 1
            assert records[0]["node"] != records[1]["node"]
            assert records[0]["node"] == driver.device_node(2**31 + 46, 0)
            want = cold_sync.restore(state["owner"], state["rows"], state["plain"],
                                     gen_history.TABLES, state["mnemonic"],
                                     records[0]["node"], cfg["now_millis"])
            assert len(want["answered"]) == 600 and want["tree"] == state["tree"]
            for record in records:
                got = driver.dump(record["db"])
                for key in driver.COMPARED:
                    assert got[key] == want["dump"][key], key
            # (e): the pulls stored nothing, and the owner's rows are upstream's.
            assert driver._owner_dump(state) == state["owner_dump"] == want["relay"]
        finally:
            driver.close(state)


def test_the_reference_answers_the_empty_tree_with_every_row_but_the_devices_own():
    tables = (("todo", ("title",)),)
    stamps = [timestamp_to_string(Timestamp(gen.BASE_MILLIS + 60_000 * i, 0, node))
              for i, node in enumerate(["a" * 16, "b" * 16, "a" * 16])]
    rows = [(t, f"ct{i}".encode()) for i, t in enumerate(stamps)]
    plain = {c: ("todo", f"row{i}", "title", f"v{i}") for i, (_t, c) in enumerate(rows)}
    got = cold_sync.restore("owner", rows, plain, tables, gen.MNEMONIC, "b" * 16,
                            gen.BASE_MILLIS + 10**6)
    assert [t for t, _c in got["answered"]] == [stamps[0], stamps[2]]
    assert got["dump"]["todo"] == [("row0", "v0"), ("row2", "v2")]
    assert got["dump"]["__clock"][0][1] != got["tree"]  # the device lacks b's row
    assert len(got["relay"][0]) == 3


# (b) the cut: one `now` a command, 65,535 messages older than the clock


@pytest.mark.parametrize("n", [65_535, 65_536])
def test_a_fold_older_than_the_clock_overflows_past_65535_in_program_and_reference(n):
    now = gen.BASE_MILLIS + 10**9
    local = Timestamp(now, 0, "a" * 16)
    millis = gen.BASE_MILLIS + np.arange(n, dtype=np.int64) // 4
    counter = np.arange(n, dtype=np.int64) % 4
    node = "b" * 16
    nodes = np.full(n, int(node, 16), dtype=np.uint64)

    def program():
        return receive_timestamps_batch_packed(local, millis, counter, nodes,
                                               lambda: [node] * n, now=now)

    def reference():
        clock = (local.millis, local.counter, local.node)
        for m, c in zip(millis.tolist(), counter.tolist()):
            clock = client_reference.receive_clock(clock, (m, c, node), now)
        return clock

    if n > 65_535:
        with pytest.raises(TimestampCounterOverflowError):
            program()
        with pytest.raises(ValueError, match="counter overflow"):
            reference()
    else:
        assert program() == Timestamp(now, n, "a" * 16)
        assert reference() == (now, n, "a" * 16)


# (c) the sync client's `sync_post`: once a round, the answer's rows and bytes


def _stage(snapshot) -> tuple:
    def counter(family):
        return sum(e["value"] for e in snapshot["counters"].get(family, [])
                   if e["labels"] == {"stage": "sync_post"})
    count = sum(e["count"] for e in snapshot["histograms"].get("evolu_stage_ms", [])
                if e["labels"] == {"stage": "sync_post"})
    return count, counter("evolu_stage_rows_total"), counter("evolu_stage_bytes_total")


def test_sync_post_is_recorded_once_a_round_with_the_answers_rows_and_bytes():
    owner = Owner("o", gen.MNEMONIC)
    clock = timestamp_to_string(Timestamp(0, 0, "c" * 16))
    messages = [CrdtMessage(timestamp_to_string(Timestamp(gen.BASE_MILLIS + i, 0, "d" * 16)),
                            "todo", f"row{i}", "title", f"v{i}") for i in range(7)]
    wire = protocol.encode_sync_response(protocol.SyncResponse(
        tuple(encrypt_messages(messages, gen.MNEMONIC)), "{}"))
    answers = [wire, urllib.error.HTTPError("u", 500, "down", {}, None)]
    received = []

    def post(url, body):
        answer = answers.pop(0)
        if isinstance(answer, Exception):
            raise answer
        return answer

    transport = SyncTransport(Config(), on_receive=lambda *a: received.append(a),
                              on_error=lambda e: None, http_post=post)
    try:
        before = _stage(metrics.registry.snapshot())
        transport.request_sync(SyncRequestInput((), clock, "{}", owner))
        transport.flush()
        after = _stage(metrics.registry.snapshot())
        assert after[0] - before[0] == 1
        assert after[1] - before[1] == 7 and after[2] - before[2] == len(wire)
        transport.request_sync(SyncRequestInput((), clock, "{}", owner))
        transport.flush()  # the server answered 500: no answer, no record
        assert _stage(metrics.registry.snapshot()) == after
        assert len(received) == 1 and len(received[0][0]) == 7
    finally:
        transport.stop()


# (d) the cell, rehearsed


@needs_native
def test_rehearsal_of_the_cell_ends_correct_with_every_new_metric():
    """`perf/run.py --rehearse --trace 1` (which runs `perf/selfcheck.py`
    first) in a process of its own: control flow, counts and `correct`,
    never a device number."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 46), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NEW_METRICS) | set(TWINS) | {
        "apply_multirow_share", "winner_read_native_share", "recv_winner_read_ms"}
    assert got["winner_read_native_share"] == 100.0  # every winner read natively
    assert got["window_compiles.restore"] == 0
    # One `Receive` of 2,000 a restore: 31 statements of 64 rows, 16 rows single.
    assert got["apply_multirow_share"] == pytest.approx(100.0 * 1984 / 2000)
    assert got["respond_fetch_ms.restore"] <= got["pass_respond_ms.restore"]
    assert got["restore_p50_s"] * 1e3 > got["sync_post_ms"] > 0
    e2e = next(l["metrics"] for l in lines if l.get("info") == "the other set")
    assert set(e2e) == {"ingest_rate", "setup_s"}
    outcome = next(l for l in lines if l.get("info") == "outcome")
    assert outcome["acked_msgs"] == 2000 * outcome["restores_ok"] == 2000 * line["attempted"]


# (e) the data files


def _layer(name: str) -> tuple:
    with open(os.path.join(ROOT, "perf", "layers", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    return spec, entry


@pytest.mark.parametrize("name", [*NEW_METRICS, *sorted(TWINS)])
def test_each_new_layer_metric_is_data_the_cell_reports(name):
    spec, entry = _layer(name)
    assert entry["workloads"] == [CELL] and spec["cells"] == ["relay-restore"]
    assert spec["moves"] == entry["moves"] == "ingest_rate"
    assert all(spec[k] == entry[k] for k in ("unit", "better", "layer"))
    assert spec["read"]["kind"] in readers.KINDS  # data: no reader of its own
    if name in TWINS:  # reads what the accepted file reads
        base_spec, base_entry = _layer(TWINS[name])
        assert spec["read"] == base_spec["read"]
        assert all(entry[k] == base_entry[k] for k in ("unit", "better", "source", "layer"))
    else:
        assert entry["layer"] == "sync client"
    # nothing to read where nothing happened: the parent's side of sync_post
    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    assert readers.read(spec["read"], empty, empty, {}) is None


def test_perf_selfcheck_accepts_the_cells_and_their_files():
    from perf import selfcheck

    selfcheck.check_files()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (config,) = [c for c in manifest["configs"] if c["name"] == "relay-restore"]
    assert config["reduced"] == ["store", "history"] and len(config["source"]) <= 200
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert len(cells) == 7 and sum(w["chips"] == 4 for w in cells.values()) == 1
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == \
        ("relay-restore", "cold-sync", 1)
    kept = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert kept == {"ingest_rate", "setup_s"}
    cfg = _config()
    assert cfg["reference"] == "cold_sync" and cfg["history"] == 60_000
    assert len(cfg["guarantees"]) == 5 and set(cfg["reduced"]) == {"store", "history"}
    with open(os.path.join(ROOT, "perf", "workloads", f"{CELL}.json")) as f:
        params = json.load(f)["params"]
    assert params == {"messages": 60_000, "concurrency": 1, "warm_restores": 1}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "perf", "reference", "cold_sync.py")) as f:
        code = [line for line in f if line.lstrip().startswith(("import ", "from "))]
    assert code and not any("evolu_tpu" in line for line in code)
