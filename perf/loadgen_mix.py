"""Closed-loop load generator of a mixed cell: one child process, a few
connection threads, one device an owner the process draws.

As `perf/loadgen.py`: the parent (which holds the chip) starts this file
as child processes with `JAX_PLATFORMS=cpu`, and the child asserts when
it ends that JAX was never imported.

Protocol, over stdin (one JSON object a line): first the spec (`url`,
`pool`, `owners`, `out`, `process`, `slots`, `seed`, `n_owners`,
`theta`, `update_share`, `msgs_per_update`, `base_millis`, `timeout_s`);
then, once the parent's warm-up is done, `{"t_go": …, "t_start": …,
"t_end": …}` on the system-wide monotonic clock. Each thread starts at
`t_go`, draws its next round (`gen_mix.draw_round` from its own
`Random(seed, slot)`) as soon as the last one is answered, and starts no
round after `t_end`. A round belongs to the device of the drawn owner in
this process (made at first use from the owner's record in the `owners`
file); a thread that draws a device in flight waits for it, outside the
timed round. One new HTTP connection a round through `gen.http_post`; no
retry: any status but 200, a timeout, or a device whose tree after its
merge is not the answer's tree is a failed round, and a device that
failed stops. Every round goes to the file `out`, with its two instants
and what the device held after its merge.
"""

import json
import os
import sys
import threading
import time
import urllib.error

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from evolu_tpu.sync import protocol  # noqa: E402
from perf import gen, gen_mix  # noqa: E402


class Devices:
    """The devices of this process, one an owner, made at first use."""

    def __init__(self, spec: dict, pool, owners: gen_mix.OwnerFile):
        self.spec, self.pool, self.owners = spec, pool, owners
        self.by_owner = {}
        self.lock = threading.Lock()

    def of(self, owner_index: int) -> gen_mix.Device:
        with self.lock:
            device = self.by_owner.get(owner_index)
            if device is None:
                owner, tree, held = self.owners.read(owner_index)
                spec = self.spec
                device = self.by_owner[owner_index] = gen_mix.Device(
                    owner, gen_mix.device_node("d", spec["process"], owner_index), tree,
                    held, spec["base_millis"], spec["msgs_per_update"], self.pool)
            return device


def one_round(device: gen_mix.Device, owner_index: int, update: bool, url: str,
              timeout_s: float) -> dict:
    """One round of a device that the caller holds → its log record. The
    timed part is body sent → answer decoded; the merge comes after."""
    body = protocol.encode_sync_request(device.request(update))
    answer, error = None, None
    t_send = time.monotonic()
    try:
        answer = protocol.decode_sync_response(gen.http_post(url, body, timeout_s))
    except (urllib.error.URLError, OSError, ValueError) as e:
        error = repr(e)
    t_done = time.monotonic()
    if answer is not None and not device.merge(answer):
        error = "after its merge the device's tree is not the answer's"
    if error is not None:
        device.failed = True
    return gen_mix.round_record(device, owner_index, update, t_send, t_done, answer, error)


def client_loop(slot: int, spec: dict, draw, devices: Devices, times, rounds: list):
    t_go, _t_start, t_end = times
    rng = gen_mix.thread_rng(spec["seed"], slot)
    time.sleep(max(0.0, t_go - time.monotonic()))
    while time.monotonic() < t_end:
        rank, update = gen_mix.draw_round(draw, rng, spec["update_share"])
        owner_index = draw.owner_of_rank[rank]
        device = devices.of(owner_index)
        with device.lock:  # a device in flight: wait for it, untimed
            if device.failed or time.monotonic() >= t_end:
                continue
            rounds.append(one_round(device, owner_index, update, spec["url"],
                                    spec["timeout_s"]))


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    pool = gen.read_pool(spec["pool"])
    owners = gen_mix.OwnerFile(spec["owners"])
    assert len(owners) == spec["n_owners"], "the owners' file is not the configuration's"
    draw = gen_mix.OwnerDraw(spec["n_owners"], spec["seed"], spec["theta"])
    devices = Devices(spec, pool, owners)
    print(json.dumps({"ready": len(spec["slots"])}), flush=True)
    go = json.loads(sys.stdin.readline())
    times = (go["t_go"], go["t_start"], go["t_end"])
    logs = [[] for _ in spec["slots"]]
    threads = [
        threading.Thread(target=client_loop, args=(slot, spec, draw, devices, times, log))
        for slot, log in zip(spec["slots"], logs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    owners.close()
    assert "jax" not in sys.modules, "the load generator imported JAX"
    with open(spec["out"], "w") as f:
        json.dump({"process": spec["process"],
                   "rounds": [r for log in logs for r in log]}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
