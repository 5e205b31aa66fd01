"""Closed-loop sync rounds of a mix against a served relay: skewed
owners, pulls and one-field updates, several devices an owner.

`clients` connections in `processes` child processes of
`perf/loadgen_mix.py` that never touch JAX, started and ready before the
driver's warm-up. Each connection draws its next round when the last one is
answered: the owner by rank from `zipf_theta` over ALL the configuration's
owners (ranks scrambled by the seed), an update (`msgs_per_update` new
messages and the device's post-apply tree) with probability
`update_share`, else a pull (no message, the device's tree as it stands).
They run from `lead_s` before the window (untimed warm-up of the real
loop) to its end. A round counts in the window when its answer was
decoded inside it; `acked_msgs` counts the messages of updates only, and
`latency_ms` holds every round, pulls and updates alike. The whole log
goes to the driver's check in `outcome["rounds"]`.
"""

import json
import os
import subprocess
import sys
import time

from perf import gen, gen_mix, load_module

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Whatever happened, no child outlives the run: the push generator's own.
cleanup = load_module("traffic", "closed_loop_push").cleanup


def prepare(state: dict, params: dict, seed: int) -> None:
    """Start the children, write the pool and the owners' records while
    they import, hand each its spec and wait until every one has loaded
    them and built its draw. The harness fixes the window's start right
    after the driver's warm-up, which here may take under a second: a
    child still loading then would miss the lead-in."""
    t0 = time.monotonic()
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    n = params["processes"]
    children = state["children"] = [
        (subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen_mix.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True),
         os.path.join(state["scratch"], f"rounds-{p}.json"))
        for p in range(n)]
    pool_path = os.path.join(state["scratch"], "pool.bin")
    owners_path = os.path.join(state["scratch"], "owners.bin")
    gen.write_pool(pool_path, state["pool"])
    gen_mix.write_owner_file(owners_path, state["requests"])
    for p, (child, out) in enumerate(children):
        child.stdin.write(json.dumps({
            "url": state["url"], "pool": pool_path, "owners": owners_path, "out": out,
            "process": p, "slots": list(range(params["clients"]))[p::n], "seed": seed,
            "n_owners": len(state["requests"]), "theta": params["zipf_theta"],
            "update_share": params["update_share"],
            "msgs_per_update": params["msgs_per_update"],
            "base_millis": state["push_base_millis"],
            "timeout_s": params["timeout_s"]}) + "\n")
        child.stdin.flush()
    for child, _out in children:
        ready = json.loads(child.stdout.readline())
        assert ready["ready"] > 0
    state["timings"]["loadgen_ready_s"] = round(time.monotonic() - t0, 3)


def account(rounds: list, msgs_per_update: int, t_start: float, seconds: float) -> dict:
    """The outcome of a log of rounds: only rounds answered inside
    [t_start, t_start + seconds) count in `attempted`, `latency_ms` and
    `acked_msgs`; `failed` and `acked_msgs_total` count every round."""
    t_end = t_start + seconds
    inside = [r for r in rounds if t_start <= r["t_done"] < t_end]
    ok = [r for r in inside if r["ok"]]
    updates = sum(1 for r in ok if r["update"])
    return {
        "attempted": len(inside),
        "failed": sum(1 for r in rounds if not r["ok"]),
        "errors": sorted({r["error"] for r in rounds if r["error"]}),
        "latency_ms": [(r["t_done"] - r["t_send"]) * 1e3 for r in ok],
        "rounds_ok": len(ok),
        "updates_ok": updates,
        "pulls_ok": len(ok) - updates,
        "answers_with_messages": sum(1 for r in ok if r["answer"]),
        "acked_msgs": updates * msgs_per_update,
        "window_s": seconds,
        "acked_msgs_total": msgs_per_update * sum(
            1 for r in rounds if r["ok"] and r["update"]),
        "msgs_per_update": msgs_per_update,
        "owners_touched": len({r["owner"] for r in rounds}),
        "rounds": rounds,
    }


def run(state: dict, params: dict, seed: int, t_start: float, seconds: float,
        window) -> dict:
    children = state["children"]
    t_end = t_start + seconds
    try:
        go = json.dumps({"t_go": t_start - params["lead_s"], "t_start": t_start,
                         "t_end": t_end}) + "\n"
        assert time.monotonic() < t_start - params["lead_s"], \
            "the lead-in was due before the children could be told"
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
        rounds = []
        for _child, out in children:
            with open(out) as f:
                rounds.extend(json.load(f)["rounds"])
    finally:
        cleanup(state)
    rounds.sort(key=lambda r: r["t_send"])
    return account(rounds, params["msgs_per_update"], t_start, seconds)
