"""Closed-loop polls (no message, an equal tree) against the served relay,
through `perf/traffic/closed_loop_poll.py`.

The relay driver's warm-up of a poll takes a millisecond and the harness
fixes the window's start right after it, so the traffic module's `prepare`
waits until every load generator's `ready` line is readable, without
reading it, and `closed_loop_push.run` reads it as it always does. Here: a
fake child that reports late, one that never does, and real children
polling a served relay.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto
from perf import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Reports ready after `delay` s, waits for the go line, writes one
# client's log of one round inside the window, exits 0.
FAKE_CHILD = """
import json, sys, time
time.sleep(float(sys.argv[1]))
print(json.dumps({"ready": 1}), flush=True)
go = json.loads(sys.stdin.readline())
time.sleep(max(0.0, go["t_end"] - time.monotonic()))
with open(sys.argv[2], "w") as f:
    json.dump({"clients": [{"slot": 0, "owner": "o", "rounds": [[go["t_start"], go["t_start"] + 0.01, True]],
                            "build_in_window_s": 0.0, "error": None, "acked_rounds": 1}]}, f)
"""


class _Window:
    def begin(self):
        self.began = time.monotonic()

    def end(self):
        self.ended = time.monotonic()


@pytest.mark.parametrize("delay", [0.0, 0.8])
def test_prepare_returns_only_once_the_ready_line_is_readable_and_run_reads_it(delay, tmp_path):
    poll = load_module("traffic", "closed_loop_poll")
    out = str(tmp_path / "samples-0.json")

    def start_children(state, params, seed):
        child = subprocess.Popen([sys.executable, "-c", FAKE_CHILD, str(delay), out],
                                 stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        state["children"] = [(child, out)]

    poll._push.prepare = start_children
    params = {"clients": 1, "processes": 1, "msgs_per_round": 0, "lead_s": 0.05,
              "timeout_s": 30}
    state = {"timings": {}}
    t0 = time.monotonic()
    poll.prepare(state, params, 0)
    assert time.monotonic() - t0 >= delay
    assert state["timings"]["loadgen_ready_s"] >= delay
    # The line was not consumed: `run` (closed_loop_push's) reads it.
    t_start = time.monotonic() + params["lead_s"] + 0.25
    outcome = poll.run(state, params, 0, t_start, 0.3, _Window())
    assert outcome["attempted"] == outcome["rounds_ok"] == 1 and outcome["failed"] == 0
    assert outcome["acked_msgs"] == 0 and outcome["latency_ms"] == [pytest.approx(10.0)]
    assert state.get("children") is None  # cleanup ran: no child outlives the run


def test_prepare_fails_where_a_child_never_reports_ready(tmp_path):
    poll = load_module("traffic", "closed_loop_poll")

    def start_children(state, params, seed):
        child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(30)"],
                                 stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        state["children"] = [(child, str(tmp_path / "never.json"))]

    poll._push.prepare = start_children
    state = {"timings": {}}
    try:
        with pytest.raises(AssertionError, match="not ready"):
            poll.prepare(state, {"timeout_s": 0.5}, 0)
    finally:
        poll.cleanup(state)


@pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the served relay's packed ingest needs both native libraries")
def test_polls_against_a_served_relay_are_answered_and_store_nothing(tmp_path):
    """`relay-reference`'s driver at its rehearsal size, the real load
    generator's children, 0 messages a round: every round answered with
    the client's own tree, nothing stored, and the window's start fixed
    only after `prepare` returned."""
    relay = load_module("drivers", "relay")
    poll = load_module("traffic", "closed_loop_poll")
    with open(os.path.join(ROOT, "perf", "configs", "relay-reference.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    params = {"clients": 4, "processes": 2, "msgs_per_round": 0, "lead_s": 0.5,
              "timeout_s": 30}
    state = relay.setup(cfg, 2**31 + 33, str(tmp_path))
    try:
        poll.prepare(state, params, 0)
        relay.warm(state, params)
        stored = relay._stored_rows(state["store"])
        t_start = time.monotonic() + params["lead_s"] + 0.25  # as perf/run.py fixes it
        outcome = poll.run(state, params, 0, t_start, 1.0, _Window())
        assert outcome["failed"] == 0 and outcome["attempted"] > 0, outcome["errors"]
        assert outcome["acked_msgs"] == outcome["acked_msgs_total"] == 0
        assert min(outcome["latency_ms"]) > 0
        assert relay._stored_rows(state["store"]) == stored == state["preload_rows"]
    finally:
        poll.cleanup(state)
        relay.close(state)
