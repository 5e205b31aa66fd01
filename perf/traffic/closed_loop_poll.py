"""Closed-loop polls against a served relay: `closed_loop_push`'s clients
with `msgs_per_round` 0, each round an equal tree and no message, so the
relay stores nothing and answers nothing.

The relay driver's warm-up then takes a millisecond, and the harness fixes
the window's start right after it: `prepare` starts the children as
`closed_loop_push.prepare` does and returns only when every child's
`ready` line is readable on its stdout, without reading it, so that
`closed_loop_push.run` reads it as it always does. `run` and `cleanup`
are `closed_loop_push`'s own.
"""

import select
import time

from perf import load_module

_push = load_module("traffic", "closed_loop_push")
run, cleanup = _push.run, _push.cleanup


def prepare(state: dict, params: dict, seed: int) -> None:
    t0 = time.monotonic()
    _push.prepare(state, params, seed)
    waiting = [child.stdout for child, _out in state["children"]]
    deadline = t0 + params["timeout_s"]
    while waiting:
        left = deadline - time.monotonic()
        assert left > 0, f"{len(waiting)} load generators not ready in {params['timeout_s']} s"
        readable, _w, _x = select.select(waiting, [], [], left)
        waiting = [f for f in waiting if f not in readable]
    state["timings"]["loadgen_ready_s"] = round(time.monotonic() - t0, 3)
