"""Tensor-valued CRDT columns (ISSUE 20).

Layers under test, host-oracle-first:
1. type-string + op codecs (ValueError-only) and the byte cap;
2. hand-model golden fixtures (tests/fixtures/crdt_tensor_golden.json
   — computed BY HAND, pinned, never updated) under every delivery
   permutation / partition / redelivery, both storage backends;
3. device twin (`ops/crdt_tensor_merge.py`) bit-identical to the
   pure-numpy host fold for every monoid (incl. the overwrite∘delta
   semidirect composition), Pallas interpret-mode parity, packed AND
   wide shard variants, jit-cache fence flat within batch buckets;
4. apply routing: tensor cells never LWW-upsert, batched ==
   sequential oracle with malformed traffic mixed in, late
   declaration folds pre-declaration ops, rebuild_state identical;
5. winner-cache contract (slot == MAX(timestamp), value == fold) and
   the client API's drain-before-observe reads.
"""

import base64
import json
import random
from pathlib import Path

import numpy as np
import pytest

from evolu_tpu.core import crdt_tensor as tz
from evolu_tpu.core import crdt_types as ct
from evolu_tpu.core.merkle import create_initial_merkle_tree
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage, TableDefinition
from evolu_tpu.obs import metrics
from evolu_tpu.storage.apply import apply_messages, apply_messages_sequential
from evolu_tpu.storage.native import native_available, open_database
from evolu_tpu.storage.schema import init_db_model, update_db_schema
from evolu_tpu.utils.config import Config

MN = "legal winner thank year wave sausage worth useful legal winner thank yellow"
GOLDEN = json.loads(
    (Path(__file__).parent / "fixtures" / "crdt_tensor_golden.json").read_text())

SCHEMA_DEF = TableDefinition.of(
    "models",
    ("name", "weights:tensor:sum:f32:2", "avg:tensor:mean:f32:2",
     "peak:tensor:max:f32:2", "grad:tensor:sum:bf16:3"))

BACKENDS = ["python"] + (["native"] if native_available() else [])


def _mk_db(backend="python"):
    db = open_database(":memory:", backend)
    init_db_model(db, MN)
    update_db_schema(db, [SCHEMA_DEF])
    return db


def _golden_msgs(section):
    t, r, c = section["cell"]
    return [CrdtMessage(op["timestamp"], t, r, c, op["value"])
            for op in section["ops"]]


def _golden_expected(section):
    cfg = tz.parse_tensor_type(section["column_type"])
    return np.asarray(section["expected_elements"],
                      np.float64).astype(tz._np_dtype(cfg))


def _ts(i, node="aaaaaaaaaaaaaaa1", base=1_700_000_000_000):
    return timestamp_to_string(Timestamp(base + i * 1000, 0, node))


# --- 1. type strings + codecs ---


def test_tensor_type_parsing():
    cfg = tz.parse_tensor_type("tensor:sum:f32:4x8")
    assert (cfg.monoid, cfg.dtype, cfg.shape) == ("sum", "f32", (4, 8))
    assert cfg.size == 32 and cfg.nbytes == 128
    assert tz.parse_tensor_type("tensor:mean:bf16:3").nbytes == 6
    assert tz.tensor_type("max", "f32", (2, 3)) == "tensor:max:f32:2x3"
    assert tz.is_tensor_type("tensor:sum:f32:1")
    assert not tz.is_tensor_type("counter")
    for bad in (
        "tensor", "tensor:sum", "tensor:sum:f32", "tensor:sum:f32:",
        "tensor:bogus:f32:2", "tensor:sum:f64:2", "tensor:sum:f32:0",
        "tensor:sum:f32:2x", "tensor:sum:f32:x2", "tensor:sum:f32:02",
        "tensor:sum:f32:-2", "tensor:sum:f32:2x3:extra",
        "tensor:sum:f32:" + "x".join(["2"] * 9),  # > _MAX_DIMS
        "tensor:sum:f32:65536",  # f32 nbytes over TENSOR_MAX_BYTES
    ):
        with pytest.raises(ValueError):
            tz.parse_tensor_type(bad)
    # The byte cap is dtype-aware: 32768 f32 elements = 128KiB > cap,
    # but the same element count in bf16 is exactly AT the 64KiB cap.
    with pytest.raises(ValueError):
        tz.parse_tensor_type("tensor:sum:f32:32768")
    assert tz.parse_tensor_type("tensor:sum:bf16:32768").nbytes == \
        tz.TENSOR_MAX_BYTES


def test_column_spec_routes_tensor_types():
    assert ct.parse_column_spec("weights:tensor:sum:f32:2x3") == \
        ("weights", "tensor:sum:f32:2x3")
    for bad in ("weights:tensor:sum:f32:nope", "weights:tensor", "a:b:c",
                ":tensor:sum:f32:2"):
        with pytest.raises(ValueError):
            ct.parse_column_spec(bad)


def test_tensor_op_codecs_valueerror_only():
    cfg = tz.parse_tensor_type("tensor:sum:f32:2")
    v = tz.tensor_delta_value(cfg, [1.5, -2.0])
    assert tz.decode_tensor_op(cfg, v) == (
        "d", np.asarray([1.5, -2.0], np.float32).tobytes(), 1)
    s = tz.tensor_set_value(cfg, [3.0, 4.0])
    assert tz.decode_tensor_op(cfg, s)[0] == "s"
    cfgm = tz.parse_tensor_type("tensor:mean:f32:2")
    vm = tz.tensor_delta_value(cfgm, [1.0, 2.0], count=7)
    assert tz.decode_tensor_op(cfgm, vm)[2] == 7
    # Encoder-side screens.
    with pytest.raises(ValueError):
        tz.tensor_delta_value(cfg, [1.0])  # wrong element count
    with pytest.raises(ValueError):
        tz.tensor_delta_value(cfg, [np.inf, 0.0])
    with pytest.raises(ValueError):
        tz.tensor_delta_value(cfg, [40000.0, 0.0])  # |v| > 2^15
    with pytest.raises(ValueError):
        tz.tensor_delta_value(cfgm, [1.0, 2.0], count=0)
    with pytest.raises(ValueError):
        tz.tensor_delta_value(cfgm, [1.0, 2.0], count=tz._COUNT_MAX + 1)
    # max skips the magnitude cap (no lattice quantization).
    cfgx = tz.parse_tensor_type("tensor:max:f32:2")
    big = tz.tensor_delta_value(cfgx, [1e30, -1e30])
    assert tz.decode_tensor_op(cfgx, big)[0] == "d"
    # Decoder: the count slot is mean's weight ONLY.
    three = json.dumps(["d", base64.b64encode(
        np.zeros(2, np.float32).tobytes()).decode(), 2])
    with pytest.raises(ValueError):
        tz.decode_tensor_op(cfg, three)  # sum rejects 3-element form
    assert tz.decode_tensor_op(cfgm, three)[2] == 2
    rng = random.Random(20)
    ok64 = base64.b64encode(np.zeros(2, np.float32).tobytes()).decode()
    corpus = [
        None, 5, 1.5, b"x", "", "{", "[]", '["d"]', '["x","%s"]' % ok64,
        '["d","not-base64!!"]', '["d","%s",1,2]' % ok64, '["d",5]',
        '["s","%s","2"]' % ok64, '["d","%s",true]' % ok64,
        '["d","%s",-1]' % ok64, '["d","' + "A" * 200000 + '"]',
        json.dumps(["d", base64.b64encode(b"abc").decode()]),  # bad length
        json.dumps(["d", base64.b64encode(
            np.asarray([np.nan, 0], np.float32).tobytes()).decode()]),
        json.dumps(["d", base64.b64encode(
            np.asarray([4e4, 0], np.float32).tobytes()).decode()]),
    ]
    corpus += ["".join(chr(rng.randrange(32, 127))
                       for _ in range(rng.randrange(0, 60)))
               for _ in range(200)]
    for cfg_i in (cfg, cfgm, cfgx):
        for c in corpus:
            try:
                tz.decode_tensor_op(cfg_i, c)
            except ValueError:
                pass  # the ONLY permitted error type


def test_schema_registry_tensor_conflicts():
    db = _mk_db()
    schema = ct.load_schema(db)
    assert schema.column_type("models", "weights") == "tensor:sum:f32:2"
    assert schema.has_typed([("models", "rX", "weights")])
    # Same full type string is idempotent; ANY parameter change raises.
    ct.declare_column_types(db, [("models", "weights", "tensor:sum:f32:2")])
    for other in ("tensor:max:f32:2", "tensor:sum:bf16:2",
                  "tensor:sum:f32:3", "counter"):
        with pytest.raises(ValueError):
            ct.declare_column_types(db, [("models", "weights", other)])


# --- 2. goldens (hand model; never update) ---


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [0, 7, 23])
@pytest.mark.parametrize("section", [k for k in GOLDEN if k != "_comment"])
def test_golden_any_order_any_partition(backend, seed, section):
    g = GOLDEN[section]
    msgs = _golden_msgs(g)
    msgs += [msgs[i] for i in g["redeliver"]]
    rng = random.Random(seed)
    rng.shuffle(msgs)
    db = _mk_db(backend)
    tree = create_initial_merkle_tree()
    i = 0
    while i < len(msgs):  # random partition into batches
        j = i + rng.randrange(1, len(msgs) - i + 1)
        tree = apply_messages(db, tree, msgs[i:j])
        i = j
    table, row, column = g["cell"]
    expected = _golden_expected(g)
    got = tz.tensor_state(db, table, row, column)
    assert got is not None and np.array_equal(got, expected), (got, expected)
    # Redelivering EVERYTHING changes nothing (op-set semantics).
    apply_messages(db, tree, msgs)
    assert np.array_equal(tz.tensor_state(db, table, row, column), expected)


@pytest.mark.parametrize("section", [k for k in GOLDEN if k != "_comment"])
def test_golden_pure_fold_oracle(section):
    """fold_cell alone (no SQL) reproduces every golden under every
    permutation — the oracle the device twin is then pinned against."""
    g = GOLDEN[section]
    cfg = tz.parse_tensor_type(g["column_type"])
    ops = []
    for op in g["ops"]:
        kind, payload, count = tz.decode_tensor_op(cfg, op["value"])
        ops.append((op["timestamp"], kind, count, payload))
    expected = _golden_expected(g).tobytes()
    rng = random.Random(99)
    for _ in range(6):
        shuffled = ops + [ops[i] for i in g["redeliver"]]
        rng.shuffle(shuffled)
        assert tz.fold_cell(cfg, shuffled) == expected


def test_golden_max_plus_zero_wins():
    """-0.0 orders strictly below +0.0 in the monotone key space: the
    materialized element is +0.0 bit-exactly."""
    g = GOLDEN["tensor_max"]
    cfg = tz.parse_tensor_type(g["column_type"])
    ops = [(op["timestamp"],) + tuple(
        tz.decode_tensor_op(cfg, op["value"])[i] for i in (0, 2, 1))
        for op in g["ops"]]
    out = np.frombuffer(tz.fold_cell(cfg, ops), np.float32)
    assert out[1] == 0.0 and not np.signbit(out[1])


# --- 3. device twin: bit parity, every monoid, packed + wide shards ---


def _random_cell_ops(rng, cfg, n_cells, max_ops):
    """{cell index: [(tag, kind, count, payload)]} with random set/delta
    mixes — raw material for both the host oracle and the device twin."""
    per_cell = {}
    t = 0
    for c in range(n_cells):
        ops = []
        for _ in range(rng.integers(1, max_ops + 1)):
            vals = (rng.random(cfg.size) * 64.0 - 32.0).astype(np.float32)
            payload = vals.astype(tz._np_dtype(cfg)).tobytes()
            kind = "s" if rng.random() < 0.25 else "d"
            count = int(rng.integers(1, 9)) if cfg.monoid == "mean" else 1
            ops.append((_ts(t), kind, count, payload))
            t += 1
        per_cell[c] = ops
    return per_cell


@pytest.mark.parametrize("type_string", [
    "tensor:sum:f32:4", "tensor:mean:bf16:3", "tensor:max:f32:5"])
@pytest.mark.parametrize("seed", [2, 17])
def test_tensor_cell_folds_match_oracle(type_string, seed):
    from evolu_tpu.ops.crdt_tensor_merge import tensor_cell_folds

    cfg = tz.parse_tensor_type(type_string)
    rng = np.random.default_rng(seed)
    n_cells = int(rng.integers(3, 40))
    per_cell = _random_cell_ops(rng, cfg, n_cells, 12)
    plans = {c: tz.contributing_ops(ops) for c, ops in per_cell.items()}
    cell_id, rows = [], []
    for c, contribs in plans.items():
        for _kind, count, payload in contribs:
            if cfg.monoid == "max":
                rows.append(tz.monotone_key(cfg, payload).astype(np.uint64))
            else:
                k = count if cfg.monoid == "mean" else 1
                rows.append(tz.quantize(cfg, payload).view(np.uint64)
                            * np.uint64(k))
            cell_id.append(c)
    cell_id = np.asarray(cell_id, np.int32)
    contrib = np.stack(rows)
    table = tensor_cell_folds(cell_id, contrib, n_cells, cfg.monoid)
    # Permutation invariance is BIT-exact (modular u64 / integer max).
    perm = rng.permutation(len(cell_id))
    table_p = tensor_cell_folds(cell_id[perm], contrib[perm], n_cells,
                                cfg.monoid)
    assert np.array_equal(table, table_p)
    for c, contribs in plans.items():
        dens = sum(k for _, k, _ in contribs) if cfg.monoid == "mean" else 1
        host = tz._fold_contributions(cfg, contribs)
        dev = tz._finalize(cfg, table[c], dens)
        assert host == dev, (type_string, c)


@pytest.mark.parametrize("variant", ["packed", "wide"])
def test_tensor_shard_sums_both_variants_match_oracle(variant):
    from evolu_tpu.ops import crdt_tensor_merge as tm

    metrics.reset()
    rng = np.random.default_rng(11)
    n, width = 2048, 3
    owner = rng.integers(0, 6, n).astype(np.int64)
    # Cell ids are globally interned (unique per owner) — the wide
    # variant's by-cell-alone segmentation contract.
    cell = (rng.integers(0, 40, n) * 6 + owner).astype(np.int64)
    if variant == "wide":
        cell = cell + (1 << 26)  # past the packed 2^25 cell budget
    contrib = rng.integers(0, 1 << 40, (n, width)).astype(np.uint64)
    got = tm.tensor_shard_sums(owner, cell, contrib)
    expect = {}
    for o, c, v in zip(owner, cell, contrib):
        key = (int(o), int(c))
        expect[key] = expect.get(key, np.zeros(width, np.uint64)) + v
    assert set(got) == set(expect)
    for key in expect:
        assert np.array_equal(got[key], expect[key].view(np.int64)), key
    assert metrics.get_counter(
        "evolu_crdt_tensor_kernel_total", variant=variant) == 1
    other = "wide" if variant == "packed" else "packed"
    assert metrics.get_counter(
        "evolu_crdt_tensor_kernel_total", variant=other) == 0
    # Partition invariance: two halves accumulate to the one-shot totals
    # (modular add — the cross-chunk contract the 2^24 chunker relies on).
    cut = n // 2
    g1 = tm.tensor_shard_sums(owner[:cut], cell[:cut], contrib[:cut])
    g2 = tm.tensor_shard_sums(owner[cut:], cell[cut:], contrib[cut:])
    for key in expect:
        acc = np.zeros(width, np.uint64)
        for g in (g1, g2):
            if key in g:
                acc += g[key].view(np.uint64)
        assert np.array_equal(acc.view(np.int64), got[key]), key


@pytest.mark.parametrize("n", [255, 4096])
def test_tensor_flat_layout_pallas_interpret_parity(n):
    """The d-major flattened scan layout produces identical u64 planes
    through the blocked XLA scan and the single-pass Pallas kernel in
    interpret mode — the same pinning discipline as test_pallas.py,
    applied to the tensor fold's tiled-flag formulation."""
    import jax

    from evolu_tpu.ops.crdt_merge import segmented_sum_scan
    from evolu_tpu.ops.pallas_scan import (
        segmented_max_scan_pallas, segmented_sum_scan_pallas)

    width = 3
    rng = np.random.default_rng(n)
    c_s = np.sort(rng.integers(0, 37, n)).astype(np.int32)
    seg = np.concatenate([[True], c_s[1:] != c_s[:-1]])
    flags = np.tile(seg, width)
    flat = rng.integers(0, 1 << 48, n * width).astype(np.uint64)
    with jax.enable_x64(True):
        blocked = np.asarray(segmented_sum_scan(
            np.asarray(flags), np.asarray(flat)))
        pal = np.asarray(segmented_sum_scan_pallas(
            np.asarray(flags), np.asarray(flat), interpret=True))
    assert np.array_equal(blocked, pal)
    from evolu_tpu.ops.merge import _segmented_max_scan
    with jax.enable_x64(True):
        m_blocked = np.asarray(_segmented_max_scan(
            np.asarray(flags), np.asarray(flat),
            np.asarray(np.zeros_like(flat)))[0])
        m_pal = np.asarray(segmented_max_scan_pallas(
            np.asarray(flags), np.asarray(flat),
            np.asarray(np.zeros_like(flat)), interpret=True)[0])
    assert np.array_equal(m_blocked, m_pal)


def test_tensor_jit_cache_flat_within_buckets():
    """Batch-bucket fence: same-bucket tensor dispatches reuse the ONE
    compiled core; only a new (bucket, width, monoid) key may add an
    entry. Guards the batch-bucket-stable-shapes invariant for the big
    fused pipeline."""
    from evolu_tpu.ops import crdt_tensor_merge as tm

    cfg = tz.parse_tensor_type("tensor:sum:f32:4")
    rng = np.random.default_rng(5)

    def _dispatch(n_ops, n_cells):
        cell_id = rng.integers(0, n_cells, n_ops).astype(np.int32)
        contrib = rng.integers(0, 1 << 40, (n_ops, 4)).astype(np.uint64)
        tm.tensor_cell_folds(cell_id, contrib, n_cells, cfg.monoid)

    _dispatch(100, 9)  # warm the (128-bucket, 16-bucket) entry
    warm = tm.tensor_cell_fold_core._cache_size()
    _dispatch(70, 12)   # same op bucket (128), same cell bucket (16)
    _dispatch(128, 16)  # exactly at the bucket edges
    assert tm.tensor_cell_fold_core._cache_size() == warm
    _dispatch(300, 9)   # new op bucket → exactly one new entry
    assert tm.tensor_cell_fold_core._cache_size() == warm + 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_device_routing_equals_host_routing_end_to_end(backend, monkeypatch):
    """Force the device fold on one replica and the host fold on the
    other: the materialized app bytes and every state table must be
    IDENTICAL — the bit-parity acceptance bar, exercised through the
    full apply path."""
    msgs = _random_tensor_log(4242)
    db_host, db_dev = _mk_db(backend), _mk_db(backend)
    monkeypatch.setattr(ct, "DEVICE_FOLD_MIN", 10**12)
    apply_messages(db_host, create_initial_merkle_tree(), msgs)
    monkeypatch.setattr(ct, "DEVICE_FOLD_MIN", 1)
    apply_messages(db_dev, create_initial_merkle_tree(), msgs)
    assert _dump_all(db_host) == _dump_all(db_dev)


def test_oversized_cell_falls_back_to_host(monkeypatch):
    """A single cell wider than one dispatch budget folds on the host
    oracle (counted) — and still lands the exact same bytes."""
    metrics.reset()
    monkeypatch.setattr(ct, "DEVICE_FOLD_MIN", 1)
    monkeypatch.setattr(tz, "DEVICE_MAX_FLAT", 8)
    cfg = tz.parse_tensor_type("tensor:sum:f32:2")
    db = _mk_db()
    msgs = [CrdtMessage(_ts(i), "models", "r1", "weights",
                        tz.tensor_delta_value(cfg, [float(i), 1.0]))
            for i in range(8)]  # 8 ops × 2 elems > 8 flat budget
    apply_messages(db, create_initial_merkle_tree(), msgs)
    assert metrics.get_counter("evolu_crdt_tensor_oversized_host_folds_total") == 1
    expect = np.asarray([sum(range(8)), 8.0], np.float32)
    assert np.array_equal(tz.tensor_state(db, "models", "r1", "weights"), expect)


# --- 4. apply routing: batched == sequential, malformed, rebuild ---


def _random_tensor_log(seed, n=160):
    """Mixed tensor + LWW traffic with malformed tensor ops sprinkled
    in, across every declared monoid/dtype, plus redelivery."""
    rng = random.Random(seed)
    nodes = ["aaaaaaaaaaaaaaa1", "bbbbbbbbbbbbbbb2"]
    cols = {
        "weights": tz.parse_tensor_type("tensor:sum:f32:2"),
        "avg": tz.parse_tensor_type("tensor:mean:f32:2"),
        "peak": tz.parse_tensor_type("tensor:max:f32:2"),
        "grad": tz.parse_tensor_type("tensor:sum:bf16:3"),
    }
    msgs = []
    for i in range(n):
        ts = timestamp_to_string(
            Timestamp(1_700_000_000_000 + i * 977, i % 3, rng.choice(nodes)))
        row = f"r{rng.randrange(4)}"
        roll = rng.random()
        if roll < 0.12:
            msgs.append(CrdtMessage(ts, "models", row, "name", f"n{i}"))
        elif roll < 0.24:  # malformed tensor ops: ignored identically
            col = rng.choice(list(cols))
            val = rng.choice(["junk", '["d","bad!"]', 5, '["s"]',
                              '["d","%s",3]' % base64.b64encode(
                                  np.zeros(2, np.float32).tobytes()).decode()])
            msgs.append(CrdtMessage(ts, "models", row, col, val))
        else:
            col = rng.choice(list(cols))
            cfg = cols[col]
            vals = [rng.uniform(-30, 30) for _ in range(cfg.size)]
            kind = tz.tensor_set_value if rng.random() < 0.3 \
                else tz.tensor_delta_value
            count = rng.randrange(1, 6) if cfg.monoid == "mean" else 1
            msgs.append(CrdtMessage(ts, "models", row, col,
                                    kind(cfg, vals, count=count)))
    msgs += rng.sample(msgs, min(len(msgs), 30))
    return msgs


def _dump_all(db):
    return (
        db.exec_sql_query('SELECT * FROM "__message" ORDER BY "timestamp"'),
        db.exec_sql_query('SELECT * FROM "models" ORDER BY "id"'),
        db.exec_sql_query(
            'SELECT * FROM "__crdt_tensor" ORDER BY "tag", "column"'),
    )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", [5, 42])
def test_batched_equals_sequential_oracle_tensor(backend, seed):
    msgs = _random_tensor_log(seed)
    db_a, db_b = _mk_db(backend), _mk_db(backend)
    with db_a.transaction():
        apply_messages_sequential(db_a, create_initial_merkle_tree(), msgs)
    apply_messages(db_b, create_initial_merkle_tree(), msgs)
    assert _dump_all(db_a) == _dump_all(db_b)


@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_matches_replay_oracle(backend):
    """End state == the pure replay_log oracle for every tensor cell —
    the same oracle the model-check episode asserts against."""
    msgs = _random_tensor_log(77, n=220)
    db = _mk_db(backend)
    apply_messages(db, create_initial_merkle_tree(), msgs)
    types = {("models", c): t for c, t in (
        ("weights", "tensor:sum:f32:2"), ("avg", "tensor:mean:f32:2"),
        ("peak", "tensor:max:f32:2"), ("grad", "tensor:sum:bf16:3"))}
    oracle = tz.replay_log(types, msgs)
    assert oracle  # the log generator must actually produce tensor cells
    for (table, row, column), expected in oracle.items():
        got = tz.tensor_state(db, table, row, column)
        assert got is not None and got.tobytes() == expected, (row, column)


def test_tensor_cells_never_lww_upsert():
    """The LARGEST-timestamp op carries a tiny delta; the app value
    must read the FOLD (base + deltas), not that op's raw payload."""
    cfg = tz.parse_tensor_type("tensor:sum:f32:2")
    msgs = [
        CrdtMessage(_ts(0), "models", "r1", "weights",
                    tz.tensor_set_value(cfg, [10.0, 20.0])),
        CrdtMessage(_ts(1), "models", "r1", "weights",
                    tz.tensor_delta_value(cfg, [0.5, -0.5])),
    ]
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    got = tz.tensor_state(db, "models", "r1", "weights")
    assert np.array_equal(got, np.asarray([10.5, 19.5], np.float32))


def test_malformed_tensor_ops_counted_and_ignored():
    metrics.reset()
    cfg = tz.parse_tensor_type("tensor:sum:f32:2")
    msgs = [
        CrdtMessage(_ts(0), "models", "r1", "weights",
                    tz.tensor_delta_value(cfg, [1.0, 2.0])),
        CrdtMessage(_ts(1), "models", "r1", "weights", "garbage"),
        CrdtMessage(_ts(2), "models", "r1", "weights", '["d","bad64!"]'),
    ]
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    assert np.array_equal(tz.tensor_state(db, "models", "r1", "weights"),
                          np.asarray([1.0, 2.0], np.float32))
    assert metrics.get_counter(
        "evolu_crdt_malformed_ops_total", type="tensor") == 2
    assert metrics.get_counter("evolu_crdt_ops_total", type="tensor") == 1
    assert metrics.get_counter(
        "evolu_crdt_tensor_ops_total", kind="delta") == 1
    # All three are in the transport log regardless (semantics untouched).
    assert len(db.exec_sql_query('SELECT * FROM "__message"')) == 3


def test_late_declaration_folds_predeclaration_tensor_ops():
    """Ops that reached __message BEFORE the tensor declaration
    (rolling upgrade) fold at declaration time — both replicas land
    identical bytes (anti-entropy could never heal a divergence)."""
    cfg = tz.parse_tensor_type("tensor:sum:f32:2")
    ops = [CrdtMessage(_ts(0), "models", "r1", "weights",
                       tz.tensor_set_value(cfg, [4.0, 8.0])),
           CrdtMessage(_ts(1), "models", "r1", "weights",
                       tz.tensor_delta_value(cfg, [1.0, -1.0]))]
    late = open_database(":memory:", "python")
    init_db_model(late, MN)
    update_db_schema(late, [TableDefinition.of("models", ("name", "weights"))])
    apply_messages(late, create_initial_merkle_tree(), ops)
    update_db_schema(late, [SCHEMA_DEF])  # the upgrade declares the type
    early = _mk_db()
    apply_messages(early, create_initial_merkle_tree(), ops)
    expect = np.asarray([5.0, 7.0], np.float32)
    for db in (late, early):
        got = tz.tensor_state(db, "models", "r1", "weights")
        assert np.array_equal(got, expect)
    # Later ops keep folding incrementally on both.
    more = [CrdtMessage(_ts(10), "models", "r1", "weights",
                        tz.tensor_delta_value(cfg, [0.5, 0.5]))]
    for db in (late, early):
        apply_messages(db, create_initial_merkle_tree(), more)
        assert np.array_equal(
            tz.tensor_state(db, "models", "r1", "weights"),
            np.asarray([5.5, 7.5], np.float32))


def test_rebuild_state_matches_incremental_tensor():
    msgs = _random_tensor_log(123, n=140)
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    before = _dump_all(db)
    ct.rebuild_state(db, ct.load_schema(db))
    assert _dump_all(db) == before


# --- 5. winner cache + client API ---


def test_winner_cache_contract_tensor_cells():
    """Tensor cells keep slot == MAX(timestamp) (the xor gate) while
    the app value is the monoid fold — same contract as the other
    typed families (test_crdt_types.py owns the counter/awset legs)."""
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"models": ("name", "weights:tensor:sum:f32:2")},
                     config=Config(backend="tpu", min_device_batch=1))
    try:
        e.worker._planner.cache.adaptive = False
        row = e.create("models", {"name": "m"})
        e.worker.flush()
        e.tensor_set("models", row, "weights", [10.0, 20.0])
        e.tensor_delta("models", row, "weights", [0.25, -0.25])
        e.tensor_delta("models", row, "weights", [0.25, -0.25])
        e.worker.flush()
        cache = e.worker._planner.cache
        assert cache is not None and cache._slots
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        checked = 0
        for (table, r, col), slot in cache._slots.items():
            got = e.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, r, col))[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}"))
            assert cached_ts == got, (table, r, col)
            if col == "weights":
                checked += 1
        assert checked == 1
        got = e.tensor_value("models", row, "weights")
        assert np.array_equal(got, np.asarray([10.5, 19.5], np.float32))
    finally:
        e.dispose()


def test_client_tensor_api_drains_before_observe():
    """tensor_value drains the worker queue first: a just-queued delta
    is visible without an explicit flush (same review finding as
    set_remove-covers-queued-add)."""
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"models": ("name", "avg:tensor:mean:f32:2")},
                     config=Config(backend="cpu"))
    try:
        row = e.create("models", {"name": "m"})
        e.tensor_set("models", row, "avg", [100.0, 200.0], count=2)
        e.tensor_delta("models", row, "avg", [5.0, 8.0], count=3)
        got = e.tensor_value("models", row, "avg")  # no flush between
        assert np.array_equal(got, np.asarray([43.0, 84.8], np.float32))
        # An undeclared column fails loudly instead of silently LWWing.
        with pytest.raises(ValueError):
            e.tensor_delta("models", row, "name", [1.0, 2.0])
    finally:
        e.dispose()


def test_reset_owner_drops_tensor_state():
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"models": ("weights:tensor:sum:f32:2",)},
                     config=Config(backend="cpu"))
    try:
        row = e.create("models", {})
        e.tensor_delta("models", row, "weights", [1.0, 2.0])
        e.worker.flush()
        assert e.db.exec_sql_query('SELECT * FROM "__crdt_tensor"')
        e.reset_owner()
        e.worker.flush()
        e.update_db_schema({"models": ("weights:tensor:sum:f32:2",)})
        e.worker.flush()
        assert e.db.exec_sql_query('SELECT * FROM "__crdt_tensor"') == []
    finally:
        e.dispose()
