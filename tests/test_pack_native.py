"""The relay pass's native request pack (ISSUE 36): `pack_requests`
(`eh_pack_requests` through ctypes.PyDLL) does `_pack_batch`'s
per-message half in one walk.

What is pinned: for every shape of batch the in-batch dedup knows (a
duplicate inside a request, across two requests of an owner, the same
timestamp under two owners, a request the dedup empties, shards left
empty, one message and thousands a request) `_pack_batch` returns, field
for field and byte for byte, what it returns with the lane switched off,
i.e. what the Python body `_pack_shards_python` packs; and for every
input the lane does not take (a `str` subclass, a timestamp of 45 or 47
characters, 46 characters that are not ASCII, a `bytearray` content, an
interpreter the ABI probe refuses) the batch demotes to that body: the
same output or the same exception, and a pass that lands counts once
under `evolu_engine_pack_total{path="python"}`.
"""

import ctypes
import zlib

import numpy as np
import pytest

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.ops import host_parse
from evolu_tpu.server.engine import BatchReconciler, _pack_shards_python
from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
from evolu_tpu.storage import native
from evolu_tpu.sync import protocol

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native host library unavailable")

BASE = 1_700_000_000_000


def _ts(i: int, node: str = "0123456789abcdef") -> str:
    return timestamp_to_string(Timestamp(BASE + i * 7, i % 5, node))


def _msgs(ixs, size=lambda i: i % 9):
    return tuple(
        protocol.EncryptedCrdtMessage(_ts(i), bytes([i % 251]) * size(i)) for i in ixs)


def _req(owner: str, messages) -> protocol.SyncRequest:
    return protocol.SyncRequest(tuple(messages), owner, "f" * 16, "{}")


def _owners_on(shard: int, shards: int, n: int):
    out, i = [], 0
    while len(out) < n:
        u = f"owner-{shard}-{i}"
        if zlib.crc32(u.encode()) % shards == shard:
            out.append(u)
        i += 1
    return out


def _store(shards: int):
    if shards == 1:
        return RelayStore(":memory:", "native")
    return ShardedRelayStore(":memory:", "native", shards=shards)


def _no_requests(_shards):
    return []


def _no_messages(_shards):
    return [_req("a", ()), _req("b", ())]


def _duplicate_inside_a_request(_shards):
    return [_req("a", _msgs([1, 2, 1, 3, 2, 2]))]


def _duplicate_across_two_requests_of_an_owner(_shards):
    # the later request carries the same timestamp with other content:
    # the first occurrence is the one kept
    again = protocol.EncryptedCrdtMessage(_ts(2), b"the later copy")
    return [_req("a", _msgs([1, 2, 3])), _req("b", _msgs([7])),
            _req("a", (again,) + _msgs([4]))]


def _one_timestamp_under_two_owners(_shards):
    return [_req("a", _msgs([1, 2])), _req("b", _msgs([2, 1]))]


def _request_emptied_by_dedup(_shards):
    return [_req("a", _msgs([1, 2, 3])), _req("a", _msgs([3, 1])), _req("a", _msgs([2, 9]))]


def _spread_with_an_empty_shard(shards):
    # every shard but the last has owners, one of them twice in the
    # batch and one with nothing to say; with one shard, that one
    requests = []
    for shard in range(max(shards - 1, 1)):
        first, second, silent = _owners_on(shard, shards, 3)
        base = 100 * shard
        requests += [_req(first, _msgs(range(base, base + 5))),
                     _req(silent, ()),
                     _req(second, _msgs(range(base + 3, base + 9)))]
    requests += [_req(_owners_on(0, shards, 1)[0], _msgs([2, 3, 50]))]
    return requests


def _one_message_a_request(shards):
    return [_req(f"owner-{i}", _msgs([i])) for i in range(3 * shards)]


def _thousands_of_messages_a_request(_shards):
    # contents of 0..40 bytes, and every 50th message of the second
    # owner a repeat of its own 49 messages earlier
    size = lambda i: i % 41  # noqa: E731
    ixs = [i - 49 if i % 50 == 49 else i for i in range(5000)]
    return [_req("a", _msgs(range(5000), size)), _req("b", _msgs(ixs, size)),
            _req("c", _msgs(range(4000, 9000), size))]


CASES = {
    "no_requests": _no_requests,
    "no_messages": _no_messages,
    "duplicate_inside_a_request": _duplicate_inside_a_request,
    "duplicate_across_two_requests_of_an_owner": _duplicate_across_two_requests_of_an_owner,
    "one_timestamp_under_two_owners": _one_timestamp_under_two_owners,
    "request_emptied_by_dedup": _request_emptied_by_dedup,
    "spread_with_an_empty_shard": _spread_with_an_empty_shard,
    "one_message_a_request": _one_message_a_request,
    "thousands_of_messages_a_request": _thousands_of_messages_a_request,
}


def _assert_same_pack(got, want):
    live, shard_data, packed, offsets, merged, rows, _path = got
    w_live, w_shard_data, w_packed, w_offsets, w_merged, w_rows, _w_path = want
    assert live == w_live and rows == w_rows and offsets == w_offsets
    assert list(shard_data) == list(w_shard_data)
    for si, (gu, gc, ts_packed, content_packed, lens) in shard_data.items():
        w_gu, w_gc, w_ts, w_content, w_lens = w_shard_data[si]
        assert (gu, gc) == (w_gu, w_gc)
        assert all(type(k) is int for k in gc)
        assert type(ts_packed) is bytes and ts_packed == w_ts
        assert type(content_packed) is bytes and content_packed == w_content
        assert lens.dtype == w_lens.dtype == np.int32
        assert lens.tolist() == w_lens.tolist()
    assert packed._buffers == w_packed._buffers and packed._offsets == w_packed._offsets
    assert list(merged) == list(w_merged)
    for owner, ix in merged.items():
        assert ix.dtype == w_merged[owner].dtype
        assert ix.tolist() == w_merged[owner].tolist()


@pytest.mark.parametrize("shards", [1, 2, 8])
@pytest.mark.parametrize("case", CASES)
def test_native_pack_is_the_python_body_byte_for_byte(case, shards, monkeypatch):
    requests = CASES[case](shards)
    eng = BatchReconciler(_store(shards))
    try:
        got = eng._pack_batch(requests)
        again = eng._pack_batch(requests)  # on the scratch the first call left
        monkeypatch.setattr(host_parse, "_PACK_LANE", None)
        want = eng._pack_batch(requests)
    finally:
        eng.close()
    assert (got[-1], again[-1], want[-1]) == ("native", "native", "python")
    _assert_same_pack(got, want)
    _assert_same_pack(again, want)
    # and the case is the one its name says
    kept = sum(len(lens) for *_x, lens in want[1].values())
    sent = sum(len(r.messages) for r in requests)
    if "duplicate" in case or "dedup" in case or "thousands" in case:
        assert kept < sent
    else:
        assert kept == sent or case == "spread_with_an_empty_shard"
    if case == "spread_with_an_empty_shard" and shards > 1:
        assert want[0] == list(range(shards - 1))
    if case == "request_emptied_by_dedup":
        assert [gc for _gu, gc, *_b in want[1].values()] == [[3, 1]]


class _Str(str):
    pass


def _with_timestamp(ts):
    return [_req("a", _msgs([1, 2])),
            _req("b", (protocol.EncryptedCrdtMessage(ts, b"x"),) + _msgs([3]))]


def _bytearray_content():
    return [_req("a", _msgs([1]) + (protocol.EncryptedCrdtMessage(_ts(2), bytearray(b"abc")),))]


class _RefusingProbe:
    """`ctypes.PyDLL` on an interpreter whose object layout the probe
    does not know: every symbol of the library but a probe that says
    no."""

    _PyDLL = ctypes.PyDLL  # the real one: the test patches the module's name

    def __init__(self, path):
        self._lib = self._PyDLL(path)

    def __getattr__(self, name):
        if name == "eh_py_abi_probe":
            return type("probe", (), {"restype": None, "argtypes": None,
                                      "__call__": lambda self, sample: 3})()
        return getattr(self._lib, name)


DEMOTIONS = {
    "str_subclass": lambda: _with_timestamp(_Str(_ts(5))),
    "45_characters": lambda: _with_timestamp(_ts(5)[:-1]),
    "47_characters": lambda: _with_timestamp(_ts(5) + "0"),
    "46_characters_not_ascii": lambda: _with_timestamp(_ts(5)[:-1] + "é"),
    "46_utf8_bytes_45_characters": lambda: _with_timestamp(_ts(5)[:-2] + "é"),
    "bytearray_content": _bytearray_content,
    "refused_probe": lambda: _with_timestamp(_ts(5)),
}
RAISES = {
    "45_characters": (ValueError, "non-canonical timestamp width in batch"),
    "47_characters": (ValueError, "non-canonical timestamp width in batch"),
    "46_characters_not_ascii": (UnicodeEncodeError, "'ascii' codec can't encode"),
    "46_utf8_bytes_45_characters": (ValueError, "non-canonical timestamp width in batch"),
}


def _pack_counts():
    return {p: metrics.get_counter("evolu_engine_pack_total", path=p)
            for p in ("native", "python")}


@pytest.mark.parametrize("case", DEMOTIONS)
def test_what_the_lane_does_not_take_demotes_to_the_python_body(case, monkeypatch):
    requests = DEMOTIONS[case]()
    if case == "refused_probe":
        monkeypatch.setattr(host_parse, "_PACK_LANE", False)  # untried
        monkeypatch.setattr(ctypes, "PyDLL", _RefusingProbe)
        assert host_parse._pack_lane() is None
    store = _store(2)
    eng = BatchReconciler(store)
    per_shard = [[] for _ in store.shards]
    for r in requests:
        per_shard[store.shard_index(r.user_id)].append(r)
    before = _pack_counts()
    try:
        if case in RAISES:
            kind, text = RAISES[case]
            with pytest.raises(kind, match=text):
                _pack_shards_python(per_shard)
            with pytest.raises(kind, match=text):
                eng._pack_batch(requests)
            with pytest.raises(kind, match=text):
                eng.reconcile(requests)
            # a pass that never landed counts under neither body
            assert _pack_counts() == before
            return
        got = eng._pack_batch(requests)
        assert got[-1] == "python"
        want = _pack_shards_python(per_shard)
        assert list(got[1]) == list(want)
        for si, (gu, gc, ts_packed, content_packed, lens) in got[1].items():
            assert (gu, gc, ts_packed, content_packed, lens.tolist()) == (
                *want[si][:4], want[si][4].tolist())
        responses = eng.reconcile(requests)
    finally:
        eng.close()
    assert len(responses) == len(requests)
    assert sorted(store.user_ids()) in (["a"], ["a", "b"])
    after = _pack_counts()
    assert (after["python"] - before["python"], after["native"] - before["native"]) == (1, 0)


def test_a_landed_native_pass_counts_once():
    eng = BatchReconciler(_store(2))
    before = _pack_counts()
    try:
        eng.reconcile(_spread_with_an_empty_shard(2))
    finally:
        eng.close()
    after = _pack_counts()
    assert (after["native"] - before["native"], after["python"] - before["python"]) == (1, 0)


def test_scratch_is_kept_and_grows_by_the_pass():
    eng = BatchReconciler(_store(1))
    try:
        eng._pack_batch([_req("a", _msgs(range(10)))])
        small = eng._pack_scratch
        eng._pack_batch([_req("a", _msgs(range(12)))])
        assert eng._pack_scratch is small  # the same power of two
        eng._pack_batch([_req("a", _msgs(range(3000)))])
        assert len(eng._pack_scratch) > len(small)
        large = eng._pack_scratch
        eng._pack_batch([_req("a", _msgs(range(10)))])
        assert eng._pack_scratch is large
    finally:
        eng.close()


def test_the_call_refuses_a_scratch_that_is_too_small():
    pack, words = host_parse._pack_lane()
    groups = [_msgs(range(100))]
    sizes, owners, per_shard = (np.array([100], np.int64), np.zeros(1, np.int32),
                                np.array([1], np.int64))
    kept, lens, out = np.empty(1, np.int64), np.empty(100, np.int32), []
    scratch = np.empty(words(100, 1) - 1, np.uint64)
    assert pack(groups, 1, sizes.ctypes.data, owners.ctypes.data, per_shard.ctypes.data, 1,
                kept.ctypes.data, lens.ctypes.data, scratch.ctypes.data, len(scratch),
                out) == 1
    assert out == []
