"""The accounting of `perf/traffic/restore_loop.py` under a fake clock
(ISSUE 29): whole restores only, over the time they took.

The first restore starts at `t_start`; none starts at or after
`t_start + seconds`; the one in flight at that moment is finished and
counted; `window_s` is the last commit's return less `t_start`; a failed
restore counts in `failed` and its messages count for nothing; the
window's edges are read before the first restore and right after the
last commit.
"""

import json
import os

import pytest

from perf import load_module, readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
restore_loop = load_module("traffic", "restore_loop")
PARAMS = {"messages": 100_000, "responses": 4, "concurrency": 1, "warm_restores": 1}


class FakeRun:
    """A clock that only the sleeps and the restores move, and the
    events in the order they happened."""

    def __init__(self, durations, errors=(), now=0.0):
        self.now = now
        self.durations = list(durations)
        self.errors = dict(errors)  # index of a restore -> its error
        self.events = []
        self.state = {"clock": lambda: self.now, "sleep": self.sleep,
                      "restore": self.restore}

    def sleep(self, seconds):
        self.events.append(("sleep", seconds))
        self.now += seconds

    def restore(self):
        i = sum(1 for e in self.events if e[0] == "restore")
        self.events.append(("restore", self.now))
        self.now += self.durations[i]
        error = self.errors.get(i)
        return {"messages": 0 if error else PARAMS["messages"], "error": error}

    def begin(self):
        self.events.append(("begin", self.now))

    def end(self):
        self.events.append(("end", self.now))

    def run(self, t_start, seconds):
        return restore_loop.run(self.state, PARAMS, 1, t_start, seconds, self)


def _rate(outcome):
    with open(os.path.join(ROOT, "perf", "metrics", "ingest_rate.json")) as f:
        return readers.read(json.load(f)["read"], None, None, outcome)


@pytest.mark.parametrize("duration, restores, window_s", [
    (2.6, 4, 10.4),   # the one in flight at t_end is finished and counted
    (2.5, 4, 10.0),   # one ends at t_end exactly: none starts at t_end
    (1.0, 10, 10.0),
    (0.99, 11, 10.89),
    (12.0, 1, 12.0),  # a restore longer than the window is still whole
], ids=["in-flight-counted", "none-starts-at-t_end", "1s", "0.99s", "longer-than-window"])
def test_whole_restores_over_the_time_they_took(duration, restores, window_s):
    fake = FakeRun([duration] * 20, now=100.0)
    out = fake.run(t_start=100.0, seconds=10.0)
    assert (out["attempted"], out["failed"], out["restores_ok"]) == (restores, 0, restores)
    assert out["acked_msgs"] == 100_000 * restores
    assert out["window_s"] == pytest.approx(window_s)
    assert out["window_s"] >= 10.0
    assert out["restore_seconds"] == pytest.approx([duration] * restores)
    assert out["restore_s"].split() == [f"{duration:.4f}"] * restores
    assert _rate(out) == pytest.approx(100_000 * restores / window_s)
    starts = [t for kind, t in fake.events if kind == "restore"]
    assert starts[0] == 100.0 and all(t < 110.0 for t in starts)
    assert starts == pytest.approx([100.0 + i * duration for i in range(restores)])


def test_unequal_restores_window_ends_with_the_last_commit():
    fake = FakeRun([3.0, 2.0, 4.5, 1.0, 9.0])
    out = fake.run(t_start=0.0, seconds=10.0)
    # starts at 0, 3, 5, 9.5; the fourth returns at 10.5; the fifth never starts
    assert out["attempted"] == 4 and out["window_s"] == pytest.approx(10.5)
    assert out["acked_msgs"] == 400_000
    assert _rate(out) == pytest.approx(400_000 / 10.5)


def test_window_edges_are_read_around_whole_restores_only():
    fake = FakeRun([2.6] * 8, now=7.0)
    fake.run(t_start=8.0, seconds=10.0)
    kinds = [e[0] for e in fake.events]
    assert kinds == ["sleep", "begin"] + ["restore"] * 4 + ["end"]
    assert fake.events[0] == ("sleep", pytest.approx(1.0))  # until t_start
    assert fake.events[1] == ("begin", 8.0)
    assert fake.events[-1] == ("end", pytest.approx(8.0 + 4 * 2.6))  # the last commit


def test_a_failed_restore_counts_in_failed_and_not_in_acked_msgs():
    fake = FakeRun([2.6] * 8, errors={1: "OnError: boom"})
    out = fake.run(t_start=0.0, seconds=10.0)
    assert (out["attempted"], out["failed"], out["restores_ok"]) == (4, 1, 3)
    assert out["acked_msgs"] == 300_000
    assert out["errors"] == ["OnError: boom"]
    assert out["window_s"] == pytest.approx(10.4)  # its time still passed
    assert _rate(out) == pytest.approx(300_000 / 10.4)


def test_a_restore_of_another_size_is_refused():
    fake = FakeRun([2.6] * 8)

    def short():
        fake.now += 2.6
        return {"messages": 99_999, "error": None}

    fake.state["restore"] = short
    with pytest.raises(AssertionError, match="99999"):
        fake.run(t_start=0.0, seconds=10.0)


def test_the_cell_files_state_the_accounting():
    with open(os.path.join(ROOT, "perf", "workloads", "client-todo.restore.json")) as f:
        cell = json.load(f)
    assert cell["generator"] == "restore_loop" and cell["traffic"] == "restore"
    assert cell["params"] == PARAMS
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert "client-todo.restore" in lists["ingest_rate"]
    assert "client-todo.restore" not in lists["sync_p50"] + lists["sync_p95"]
    assert lists["setup_s"] is None  # every cell reports it
