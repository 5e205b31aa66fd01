"""Driver of a restoring client whose owner's history has a time shape
(`perf/gen_history.py`: sessions over `days` days, tens of thousands of
Merkle minutes). Only the set-up differs from `perf/drivers/client.py`:
the restore, the warm-up, the check against the plain reference and the
close are that file's own functions, not copies.
"""

import time

from perf import gen_history, load_module, observe

client = load_module("drivers", "client")
restore, warm, check, close = client.restore, client.warm, client.check, client.close


def setup(cfg: dict, seed: int, scratch: str) -> dict:
    observe.assert_native()
    t0 = time.monotonic()
    messages = gen_history.build_messages(
        cfg["messages"], seed, cfg["rows_per_table"], cfg["nodes"],
        cfg["days"], cfg["sessions_per_day"], cfg["session_minutes"])
    t1 = time.monotonic()
    wires = gen_history.build_responses(messages, cfg["responses"], gen_history.MNEMONIC)
    t2 = time.monotonic()
    state = {
        "cfg": cfg, "seed": seed, "mnemonic": gen_history.MNEMONIC,
        "messages": messages, "wires": wires, "restores": [], "warm_restores": 0,
        "timings": {"history_s": round(t1 - t0, 3), "responses_s": round(t2 - t1, 3),
                    "wire_bytes": sum(len(w) for w in wires),
                    "minutes": len({m.timestamp[:16] for m in messages})},
    }
    state["restore"] = lambda: restore(state)
    return state
