"""Whole units of work, back to back, counted whole.

One device at a time (`concurrency` 1): `state["restore"]()` runs one
restore from the empty database to the last commit and returns its
record (`messages` committed, `error` or None); the next starts when it
has returned. The first starts at `t_start`. None starts at or after
`t_start + seconds`; the one in flight at that moment is finished and
counted, so the window holds whole restores only and ends with the last
commit: `window_s` is that commit's return less `t_start`, and the rate
is messages of successful restores over it. A failed restore counts in
`failed` and its messages count for nothing. The clock and the sleep
are the state's where it has them (the accounting's test), the
process's otherwise.
"""

import time


def run(state: dict, params: dict, seed: int, t_start: float, seconds: float,
        window) -> dict:
    assert params["concurrency"] == 1, "one device is one DbWorker"
    clock = state.get("clock", time.monotonic)
    sleep = state.get("sleep", time.sleep)
    restore = state["restore"]
    t_end = t_start + seconds
    sleep(max(0.0, t_start - clock()))
    window.begin()
    records, restore_s = [], []
    t_last = t_start
    while True:
        t0 = clock()
        if t0 >= t_end:
            break
        records.append(restore())
        t_last = clock()
        restore_s.append(t_last - t0)
    window.end()

    ok = [r for r in records if r["error"] is None]
    for r in ok:
        assert r["messages"] == params["messages"], \
            f"a restore committed {r['messages']} messages, not {params['messages']}"
    return {
        "attempted": len(records),
        "failed": len(records) - len(ok),
        "errors": sorted({r["error"] for r in records if r["error"] is not None}),
        "restores_ok": len(ok),
        "acked_msgs": sum(r["messages"] for r in ok),
        "window_s": t_last - t_start,
        "restore_seconds": restore_s,
        # a string, so that the harness's line of information carries it
        "restore_s": " ".join(f"{s:.4f}" for s in restore_s),
    }
