"""Seeded generator of an owner's history with a time shape: the
messages of `perf/gen_client.py`, spread over the active minutes of
`days` days instead of 25 seconds, so that the client's Merkle tree
(one leaf a distinct minute, `merkleTree.ts`) has a deployment's size.

A day has `sessions_per_day` sessions, one in each equal slot of the
day, starting where `seed` says inside its slot and lasting
`session_minutes` consecutive minutes; the history's active minutes are
those of every session, in order, and never overlap. The split into
responses and their encryption are `perf/gen_client.py`'s, imported,
not copied. Set-up only: built once a run, untimed. Module level imports
nothing of JAX.
"""

import random

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from perf.gen import BASE_MILLIS
from perf.gen_client import (  # noqa: F401 - the driver takes them from here
    MNEMONIC, TABLES, build_responses, split_responses)

DAY_MINUTES = 24 * 60
BASE_MINUTE = -(-BASE_MILLIS // 60_000)  # the first whole minute at or after the base


def active_minutes(seed: int, days: int, sessions_per_day: int,
                   session_minutes: int) -> list:
    """Minutes since the epoch of every active minute, ascending:
    `days x sessions_per_day x session_minutes` of them, all distinct."""
    slot = DAY_MINUTES // sessions_per_day
    assert 0 < session_minutes <= slot, "a session does not fit its slot of the day"
    rng = random.Random(f"{seed}-sessions")  # its own stream: the messages' draws stay gen_client's
    out = []
    for day in range(days):
        for s in range(sessions_per_day):
            start = (BASE_MINUTE + day * DAY_MINUTES + s * slot
                     + rng.randrange(slot - session_minutes + 1))
            out.extend(range(start, start + session_minutes))
    return out


def message_minutes(n: int, minutes: list) -> list:
    """The minute of each of `n` messages: dealt over `minutes` in
    order, message i to minute `i * len(minutes) // n`."""
    return [minutes[i * len(minutes) // n] for i in range(n)]


def build_messages(n: int, seed: int, rows: int, nodes: int, days: int,
                   sessions_per_day: int, session_minutes: int) -> list:
    """`n` cell writes of one owner in strictly increasing HLC order,
    every timestamp unique. The messages dealt to one minute are one
    burst, as a mutation's column writes are (`send_timestamp` once a
    message: one millis, the counter counting up): their millis is the
    minute's start plus an offset by `seed`, their counters 0, 1, 2, ….
    Table, row, column, writer node and value are drawn exactly as
    `perf/gen_client.py::build_messages` draws them, from the same
    stream in the same order."""
    rng = random.Random(seed)
    offsets = random.Random(f"{seed}-offsets")
    writers = [f"{rng.getrandbits(64):016x}" for _ in range(nodes)]
    minutes = message_minutes(
        n, active_minutes(seed, days, sessions_per_day, session_minutes))
    out = []
    minute, millis, counter = None, 0, 0
    for i in range(n):
        if minutes[i] != minute:
            minute = minutes[i]
            millis, counter = minute * 60_000 + offsets.randrange(60_000), 0
        else:
            counter += 1
        table, cols = rng.choice(TABLES)
        out.append(CrdtMessage(
            timestamp_to_string(Timestamp(millis, counter, rng.choice(writers))),
            table, f"row{rng.randrange(rows)}", rng.choice(cols), f"v{i}"))
    return out
