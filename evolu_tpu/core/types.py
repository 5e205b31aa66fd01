"""Core CRDT data types and errors.

Mirrors the reference's type surface (reference:
packages/evolu/src/types.ts) with Python dataclasses. A `CrdtValue` is
`None | str | int | float` (types.ts:88). Messages address a single
(table, row, column) cell and carry an HLC timestamp that totally
orders all writes (types.ts:90-99).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Optional, Union

CrdtValue = Union[None, str, int, float]

MAX_COUNTER = 65535  # types.ts:54
MAX_DRIFT_DEFAULT = 60000  # config.ts:9


@dataclass(frozen=True)
class Timestamp:
    """Hybrid logical clock timestamp (types.ts:60-64).

    `millis` is wall-clock ms since epoch, `counter` in [0, 65535],
    `node` a 16-lowercase-hex-char node id. The string encoding is
    fixed-width so lexicographic string order equals (millis, counter,
    node) order — LWW comparisons are plain string `<`.
    """

    millis: int
    counter: int
    node: str


@dataclass(frozen=True)
class NewCrdtMessage:
    """A cell write not yet stamped with a timestamp (types.ts:90-95)."""

    table: str
    row: str
    column: str
    value: CrdtValue


@dataclass(frozen=True)
class CrdtMessage:
    """A stamped cell write (types.ts:97-99). `timestamp` is the string encoding."""

    timestamp: str
    table: str
    row: str
    column: str
    value: CrdtValue


@dataclass(frozen=True)
class CrdtClock:
    """Per-replica clock state persisted in __clock (types.ts:101-104)."""

    timestamp: Timestamp
    merkle_tree: Mapping  # a dict, or core.merkle.MerkleText


# --- Errors (types.ts:315-399). Raised as exceptions; the runtime
# converts them into onError outputs like the reference's Either channel.


class EvoluError(Exception):
    """Base class for all framework errors."""

    type: str = "EvoluError"

    def to_dict(self) -> dict:
        return {"type": self.type}


class TimestampDriftError(EvoluError):
    type = "TimestampDriftError"

    def __init__(self, next_millis: int, now: int):
        super().__init__(f"clock drift: next={next_millis} now={now}")
        self.next = next_millis
        self.now = now

    def to_dict(self) -> dict:
        return {"type": self.type, "next": self.next, "now": self.now}


class TimestampCounterOverflowError(EvoluError):
    type = "TimestampCounterOverflowError"

    def __init__(self) -> None:
        super().__init__("HLC counter overflow (> 65535)")


class TimestampDuplicateNodeError(EvoluError):
    type = "TimestampDuplicateNodeError"

    def __init__(self, node: str):
        super().__init__(f"duplicate node id: {node}")
        self.node = node

    def to_dict(self) -> dict:
        return {"type": self.type, "node": self.node}


class TimestampParseError(EvoluError):
    type = "TimestampParseError"


class SyncError(EvoluError):
    """Replica can't converge: repeated identical Merkle diff (types.ts:371-378)."""

    type = "SyncError"

    def __init__(self) -> None:
        super().__init__("sync livelock: repeated identical merkle diff")


class SQLiteError(EvoluError):
    type = "SQLiteError"


class ValidationError(EvoluError):
    """A model brand rejected a value (format or length)."""

    type = "ValidationError"


class StringMaxLengthError(ValidationError):
    type = "StringMaxLengthError"


class UnknownError(EvoluError):
    type = "UnknownError"

    def __init__(self, error: object):
        super().__init__(str(error))
        self.error = error

    def to_dict(self) -> dict:
        return {"type": self.type, "error": {"message": str(self.error)}}


class NonCanonicalStoreError(UnknownError):
    """A stored relay timestamp is not the canonical 46-byte width, so
    the packed C fetch paths (which assume fixed-width rows) cannot
    serve it. Callers fall back to the generic SQL path — a single
    malformed stored row must degrade that owner's sync to the slow
    path, not wedge it (advisor r4)."""

    type = "UnknownError"  # wire-visible type is unchanged


@dataclass(frozen=True)
class Owner:
    """A database owner: identity derived from a BIP39 mnemonic (types.ts:149-153)."""

    id: str
    mnemonic: str


@dataclass(frozen=True)
class TableDefinition:
    name: str
    columns: tuple

    @staticmethod
    def of(name: str, columns) -> "TableDefinition":
        return TableDefinition(name, tuple(columns))
