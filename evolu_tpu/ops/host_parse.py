"""Vectorized host-side batch parsing (numpy).

The end-to-end system path was dominated by per-message Python work —
`timestamp_from_string` + pure-Python murmur per message while
columnarizing (the reference's hot loop #4 reborn on the host). These
helpers parse a whole batch of canonical 46-char timestamp strings and
intern cells with numpy, leaving no per-message Python in the batched
apply path.

Strictness: timestamps must be exactly the reference's fixed-width
encoding `YYYY-MM-DDTHH:mm:ss.sssZ-CCCC-node16` (timestamp.ts:43-48);
any malformed row raises TimestampParseError, aborting the enclosing
transaction exactly like the scalar parser would.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from evolu_tpu.core.types import TimestampParseError

_LEN = 46


def _days_from_civil(y, m, d):
    """Inverse of Howard Hinnant's civil_from_days, vectorized int64."""
    y = y - (m <= 2)
    era = np.floor_divide(y, 400)
    yoe = y - era * 400
    mp = m + np.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _days_in_month(y, m):
    """Vectorized month lengths with Gregorian leap rules."""
    lengths = np.array([0, 31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])
    days = lengths[m]
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    return np.where((m == 2) & leap, 29, days)


def parse_timestamp_strings(
    timestamps: Sequence[str], with_case: bool = False
):
    """Batch `timestampFromString`: → (millis int64, counter int32,
    node uint64). Validates the full fixed-width layout.

    With `with_case=True`, appends a per-row bool array: True where the
    row uses the canonical encoder's hex case (UPPERCASE counter,
    lowercase node — timestamp.ts:43-48). Computed from the
    already-built byte buffer, so the screen costs two slice compares,
    not a second join+scan. Callers quarantine non-canonical rows to
    host paths: the device kernels order by numeric keys and hash a
    canonical re-render, which matches the reference's raw-string
    order / verbatim-node hash only for canonical strings."""
    n = len(timestamps)
    if n == 0:
        empty = (np.empty(0, np.int64), np.empty(0, np.int32), np.empty(0, np.uint64))
        return (*empty, np.ones(0, bool)) if with_case else empty
    # Per-string length check FIRST: a joined-length check alone would
    # accept e.g. ["", "<two valid stamps concatenated>"] after reshape.
    if any(len(t) != _LEN for t in timestamps):
        raise TimestampParseError("malformed timestamp in batch")
    joined = "".join(timestamps)
    if not joined.isascii():
        raise TimestampParseError("malformed timestamp in batch")
    packed = joined.encode("ascii")
    native = parse_packed_timestamps(packed, n, with_case=with_case, strict=False)
    if native is not None:
        return native
    buf = np.frombuffer(packed, np.uint8).reshape(n, _LEN)

    # Fixed separators.
    seps = {4: ord("-"), 7: ord("-"), 10: ord("T"), 13: ord(":"), 16: ord(":"),
            19: ord("."), 23: ord("Z"), 24: ord("-"), 29: ord("-")}
    for pos, ch in seps.items():
        if not (buf[:, pos] == ch).all():
            raise TimestampParseError("malformed timestamp in batch")

    def dec(a, b):
        cols = buf[:, a:b]
        if ((cols < ord("0")) | (cols > ord("9"))).any():
            raise TimestampParseError("malformed timestamp in batch")
        v = np.zeros(n, np.int64)
        for i in range(a, b):
            v = v * 10 + (buf[:, i].astype(np.int64) - ord("0"))
        return v

    y, mo, d = dec(0, 4), dec(5, 7), dec(8, 10)
    hh, mi, ss, ms = dec(11, 13), dec(14, 16), dec(17, 19), dec(20, 23)
    # Field-range validation, matching the scalar parser's datetime
    # constructor (a month 13 or hour 25 must abort, not wrap).
    if (
        (y < 1).any()  # datetime's MINYEAR — year 0000 must abort
        or (mo < 1).any() or (mo > 12).any()
        or (d < 1).any() or (d > _days_in_month(y, mo)).any()
        or (hh > 23).any() or (mi > 59).any() or (ss > 59).any()
    ):
        raise TimestampParseError("malformed timestamp in batch")
    days = _days_from_civil(y, mo, d)
    millis = ((days * 86400 + hh * 3600 + mi * 60 + ss) * 1000) + ms

    def hexv(a, b):
        # Both hex cases accepted, like the scalar parser (the canonical
        # encoder emits uppercase counter / lowercase node, but wire
        # strings may be non-canonical and must parse identically on
        # every backend).
        v = np.zeros(n, np.uint64)
        for i in range(a, b):
            c = buf[:, i]
            digit = (c >= ord("0")) & (c <= ord("9"))
            lower = (c >= ord("a")) & (c <= ord("f"))
            upper = (c >= ord("A")) & (c <= ord("F"))
            if ((~digit) & (~lower) & (~upper)).any():
                raise TimestampParseError("malformed timestamp in batch")
            nib = np.where(
                digit, c - ord("0"),
                np.where(lower, c - ord("a") + 10, c - ord("A") + 10),
            ).astype(np.uint64)
            v = (v << np.uint64(4)) | nib
        return v

    counter = hexv(25, 29).astype(np.int32)
    node = hexv(30, 46)
    if with_case:
        cb, nb = buf[:, 25:29], buf[:, 30:46]
        case_ok = ~(
            ((cb >= ord("a")) & (cb <= ord("f"))).any(axis=1)
            | ((nb >= ord("A")) & (nb <= ord("F"))).any(axis=1)
        )
        return millis, counter, node, case_ok
    return millis, counter, node


def parse_packed_timestamps(
    packed: bytes, n: int, with_case: bool = False, strict: bool = True
):
    """Native (C) batch parse over an already-packed buffer of n
    46-byte records — one pass instead of ~40 vectorized numpy passes,
    and no join when the caller already built the buffer (the packed
    relay ingest reuses its insert buffer here).

    Returns the same tuple as `parse_timestamp_strings`. With
    `strict=False`, returns None when the native library is
    unavailable so the caller can fall back to numpy."""
    from evolu_tpu.storage.native import load_library

    lib = load_library()
    if lib is None:
        if strict:
            raise RuntimeError("native host library unavailable")
        return None
    if len(packed) != n * _LEN:
        raise TimestampParseError("malformed timestamp in batch")
    import ctypes

    millis = np.empty(n, np.int64)
    counter = np.empty(n, np.int32)
    node = np.empty(n, np.uint64)
    case_ok = np.empty(n, np.uint8)
    rc = lib.eh_parse_timestamps(
        packed, n,
        millis.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        counter.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        node.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        case_ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    if rc != 0:
        raise TimestampParseError("malformed timestamp in batch")
    if with_case:
        return millis, counter, node, case_ok.astype(bool)
    return millis, counter, node


_PACK_LANE = False  # resolved lazily: False = untried, None = unavailable


def _pack_lane():
    """The CPython-ABI request pack → (`eh_pack_requests`,
    `eh_pack_scratch_words`) through `open_gil_held`'s second handle on
    libevolu_host.so (the walk over the message objects needs the GIL;
    `storage/native.py`'s handle drops it), or None: then the caller's
    Python body is the path."""
    global _PACK_LANE
    if _PACK_LANE is not False:
        return _PACK_LANE
    _PACK_LANE = None
    import ctypes as c

    from evolu_tpu.storage.native import load_library
    from evolu_tpu.utils.native_loader import open_gil_held

    plib = open_gil_held("libevolu_host.so", "eh_py_abi_probe") if load_library() else None
    if plib is not None:  # the probe is there, so the two are
        words = plib.eh_pack_scratch_words
        words.restype = c.c_int64
        words.argtypes = [c.c_int64, c.c_int64]
        pack = plib.eh_pack_requests
        pack.restype = c.c_int
        pack.argtypes = [
            c.py_object, c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
            c.py_object,
        ]
        _PACK_LANE = (pack, words)
    return _PACK_LANE


def pack_requests(groups: list, owners: Sequence[int],
                  shard_groups: Sequence[int], scratch=None):
    """One native walk over a relay pass's messages: the in-batch dedup
    on (timestamp, owner) and the packed buffers of every shard.

    `groups` lists the requests' `messages` sequences (of objects with
    a `timestamp` str and a `content` bytes), shard after shard;
    `owners[g]` is group g's owner as a dense id (equal ids, one
    owner); shard s takes the next `shard_groups[s]` groups. `scratch`
    is what an earlier call returned (the table and the row index the
    walk needs: kept by the caller so that a pass touches no fresh
    pages for them), or None.

    → (kept, lens, buffers, scratch): rows of each group that survived
    the dedup (first occurrence in walk order), the kept rows' content
    lengths (int32, all shards back to back; the caller slices), and
    for every shard with a kept row, in shard order, two `bytes`:
    rows x 46 timestamp bytes and the packed contents. None where the
    lane is unavailable or declines the batch (a timestamp that is not
    an exact 46-character ASCII `str`, a content that is not exact
    `bytes`, any CPython error): the caller's Python body then packs
    the batch and raises what it raises."""
    lane = _pack_lane()
    if lane is None:
        return None
    pack, scratch_words = lane
    n_groups = len(groups)
    sizes = np.fromiter(map(len, groups), np.int64, count=n_groups)
    owner_ids = np.fromiter(owners, np.int32, count=n_groups)
    per_shard = np.fromiter(shard_groups, np.int64, count=len(shard_groups))
    n_rows = int(sizes.sum())
    if scratch is None or len(scratch) < scratch_words(n_rows, len(per_shard)):
        # By the power of two above, so that passes of about one size
        # share one allocation.
        scratch = np.empty(
            scratch_words(1 << max(n_rows - 1, 0).bit_length(), len(per_shard)),
            np.uint64)
    kept = np.empty(n_groups, np.int64)
    lens = np.empty(n_rows, np.int32)
    buffers: list = []
    rc = pack(groups, n_groups, sizes.ctypes.data, owner_ids.ctypes.data,
              per_shard.ctypes.data, len(per_shard), kept.ctypes.data,
              lens.ctypes.data, scratch.ctypes.data, len(scratch), buffers)
    if rc != 0:
        return None
    return kept, lens, buffers, scratch


def intern_cells(
    tables: Sequence[str], rows: Sequence[str], columns: Sequence[str]
) -> Tuple[np.ndarray, List[Tuple[str, str, str]]]:
    """→ (cell_id int32 per message, unique cell tuples indexed by id).

    First-appearance interning like the dict-based scalar path (ids are
    dense 0..k-1 in order of first occurrence)."""
    # Length-prefixed keys: a separator byte inside a field can never
    # collide two distinct cells (fields arrive from untrusted peers).
    keys = np.array(
        [f"{len(t)}.{len(r)}.{t}{r}{c}" for t, r, c in zip(tables, rows, columns)],
        dtype=object,
    )
    _, first_idx, inverse = np.unique(keys, return_index=True, return_inverse=True)
    # np.unique sorts; remap to first-appearance order for parity with
    # the scalar intern (and deterministic cell ids).
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    cell_id = rank[inverse].astype(np.int32)
    uniq_positions = first_idx[order]
    cells = [
        (tables[i], rows[i], columns[i]) for i in uniq_positions
    ]
    return cell_id, cells
