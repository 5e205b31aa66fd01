"""Closed-loop sync rounds against a served relay.

`clients` clients, each its own owner, each round pushing
`msgs_per_round` new messages with the client's own post-apply tree (0
messages is a poll: an equal tree, nothing to store); the next round
leaves when the answer is decoded. The clients live in `processes` child
processes of `perf/loadgen.py` that never touch JAX, started while the
relay still warms up; they run from `lead_s` before the window (untimed
warm-up of the real loop) to its end. A round counts in the window when
its answer was decoded inside it.
"""

import json
import os
import subprocess
import sys
import time

from perf import gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def prepare(state: dict, params: dict, seed: int) -> None:
    """Start the children; they load the pool and build their clients
    while the driver warms the relay up."""
    pool_path = os.path.join(state["scratch"], "pool.bin")
    gen.write_pool(pool_path, state["pool"])
    clients = [
        {"slot": i, "owner": r.user_id, "tree": r.merkle_tree,
         "base_millis": state["push_base_millis"]}
        for i, r in enumerate(state["client_requests"][:params["clients"]])]
    assert len(clients) == params["clients"], "the configuration reserves too few owners"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    children = []
    n = params["processes"]
    for p in range(n):
        out = os.path.join(state["scratch"], f"samples-{p}.json")
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)
        child.stdin.write(json.dumps({
            "url": state["url"], "pool": pool_path, "out": out,
            "msgs": params["msgs_per_round"], "timeout_s": params["timeout_s"],
            "clients": clients[p::n]}) + "\n")
        child.stdin.flush()
        children.append((child, out))
    state["children"] = children


def cleanup(state: dict) -> None:
    """Whatever happened, no child outlives the run."""
    for child, _out in state.pop("children", []):
        if child.poll() is None:
            child.kill()
        child.wait()


def run(state: dict, params: dict, seed: int, t_start: float, seconds: float,
        window) -> dict:
    children = state["children"]
    t_end = t_start + seconds
    try:
        for child, _out in children:
            ready = json.loads(child.stdout.readline())
            assert ready["ready"] > 0
        go = json.dumps({"t_go": t_start - params["lead_s"], "t_start": t_start,
                         "t_end": t_end}) + "\n"
        assert time.monotonic() < t_start - params["lead_s"], \
            "the load generators were not ready before the lead-in"
        for child, _out in children:
            child.stdin.write(go)
            child.stdin.flush()
        time.sleep(max(0.0, t_start - time.monotonic()))
        window.begin()
        time.sleep(max(0.0, t_end - time.monotonic()))
        for child, _out in children:
            rc = child.wait(timeout=params["timeout_s"] + 60)
            assert rc == 0, f"a load generator exited with {rc}"
        window.end()
        clients = []
        for _child, out in children:
            with open(out) as f:
                clients.extend(json.load(f)["clients"])
    finally:
        cleanup(state)

    msgs = params["msgs_per_round"]
    clients.sort(key=lambda c: c["slot"])
    inside = [r for c in clients for r in c["rounds"] if t_start <= r[1] < t_end]
    ok = [r for r in inside if r[2]]
    build_s = sum(c["build_in_window_s"] for c in clients)
    return {
        "attempted": len(inside),
        "failed": sum(1 for c in clients for r in c["rounds"] if not r[2]),
        "errors": sorted({c["error"] for c in clients if c["error"]}),
        "latency_ms": [(r[1] - r[0]) * 1e3 for r in ok],
        "rounds_ok": len(ok),
        "acked_msgs": len(ok) * msgs,
        "window_s": seconds,
        "acked_msgs_total": sum(c["acked_rounds"] for c in clients) * msgs,
        "msgs_per_round": msgs,
        "loadgen_build_share": 100.0 * build_s / (len(clients) * seconds),
        "clients": [{"slot": c["slot"], "owner": c["owner"],
                     "acked_rounds": c["acked_rounds"]} for c in clients],
    }
