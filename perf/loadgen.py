"""Closed-loop load generator: one child process, a few client threads.

Real clients are other machines, so building request bodies must never
hold the relay's interpreter lock: the parent (which holds the chip)
starts this file as child processes with `JAX_PLATFORMS=cpu`, and the
child asserts when it ends that JAX was never imported.

Protocol, over stdin (one JSON object a line): first the spec (`url`,
`pool`, `msgs`, `timeout_s`, `out` and the `clients`, each `slot`,
`owner`, `tree`, `base_millis`); then, once the parent's warm-up is done,
`{"t_go": …, "t_start": …, "t_end": …}` on the system-wide monotonic
clock. Each client starts at `t_go`, sends its next round as soon as the
last one is answered, and starts no round after `t_end`. One new HTTP
connection a round through `urllib`, as `sync.client._http_post` makes
them; no retry: any status but 200, a timeout, or an answer that is not
"nothing to fetch, tree equal to my own fold" is a failed round, and a
client that failed stops (its store state is no longer known). Samples go
to the file `out` as one JSON object.
"""

import json
import os
import sys
import threading
import time
import urllib.error

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perf import gen  # noqa: E402


def client_loop(client, url, timeout_s, times, out):
    """→ out: rounds as (t_send, t_done, ok), build seconds inside the
    window, and the error of a failed round."""
    t_go, t_start, t_end = times
    rounds, build_in_window = [], 0.0
    error = None
    time.sleep(max(0.0, t_go - time.monotonic()))
    while True:
        t0 = time.monotonic()
        if t0 >= t_end:
            break
        body = client.next_body()
        t_send = time.monotonic()
        build_in_window += max(0.0, min(t_send, t_end) - max(t0, t_start))
        try:
            ok = client.answered(gen.http_post(url, body, timeout_s))
            if not ok:
                error = "answer is not (no messages, my own tree)"
        except (urllib.error.URLError, OSError, ValueError) as e:
            ok, error = False, repr(e)
        rounds.append((t_send, time.monotonic(), ok))
        if not ok:
            break
    out.update(slot=client.slot, owner=client.owner, rounds=rounds,
               build_in_window_s=build_in_window, error=error,
               acked_rounds=sum(1 for r in rounds if r[2]))


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    pool = gen.read_pool(spec["pool"])
    clients = [
        gen.PushClient(c["slot"], c["owner"], c["tree"], c["base_millis"],
                       spec["msgs"], pool)
        for c in spec["clients"]]
    print(json.dumps({"ready": len(clients)}), flush=True)
    go = json.loads(sys.stdin.readline())
    times = (go["t_go"], go["t_start"], go["t_end"])
    outs = [{} for _ in clients]
    threads = [
        threading.Thread(target=client_loop,
                         args=(c, spec["url"], spec["timeout_s"], times, o))
        for c, o in zip(clients, outs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert "jax" not in sys.modules, "the load generator imported JAX"
    with open(spec["out"], "w") as f:
        json.dump({"clients": outs}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
