"""Checks of the yardstick itself; no JAX, no chip.

Run by `python perf/selfcheck.py` and by `run.py --rehearse` before
anything else: the interval-union busy share on hand-made intervals, the
percentile arithmetic on a known list, the readers on hand-made registry
snapshots, the generators' own hash and fold against the program's, and that every data file under `perf/` and every entry of
`BENCHMARK.json` names only modules, metrics and cells that exist, in the
characters the manifest allows.
"""

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perf import readers  # noqa: E402
from perf.stats import percentile, union_seconds  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_union() -> None:
    assert union_seconds([(0, 4), (2, 6)], 0, 10) == 6          # overlap
    assert union_seconds([(0, 10), (2, 3), (4, 5)], 0, 10) == 10  # containment
    assert union_seconds([(0, 2), (5, 6)], 0, 10) == 3          # a gap
    assert union_seconds([(-5, 1), (9, 20), (30, 40)], 0, 10) == 2  # outside the slice
    assert union_seconds([], 0, 10) == 0
    assert union_seconds([(1, 1)], 0, 10) == 0
    from perf.reduce import busy_seconds

    assert busy_seconds([(0, 2_000_000_000), (1_000_000_000, 3_000_000_000),
                         (5_000_000_000, 6_000_000_000)]) == 4.0


def check_percentile() -> None:
    xs = list(range(1, 101))  # 1..100
    assert percentile(xs, 50) == 50.5
    assert abs(percentile(xs, 95) - 95.05) < 1e-9
    assert percentile(xs, 0) == 1 and percentile(xs, 100) == 100
    assert percentile([7], 95) == 7
    assert percentile([3, 1, 2], 50) == 2  # sorts its input


def check_readers() -> None:
    def snap(c, hs, hc):
        return {"counters": {"f": [{"labels": {"stage": "a"}, "value": c},
                                   {"labels": {"stage": "b"}, "value": 100.0}],
                             "g": [{"labels": {"stage": "a"}, "value": 2 * c}]},
                "gauges": {},
                "histograms": {"h": [{"labels": {"stage": "a"}, "sum": hs, "count": hc}]}}

    before, after = snap(10.0, 5.0, 2), snap(30.0, 35.0, 12)
    ratio = {"kind": "counter_ratio", "scale": 10.0,
             "num": [["f", {"stage": "a"}]], "den": [["g", {"stage": "a"}]]}
    assert readers.read(ratio, before, after, {}) == 5.0
    mean = {"kind": "hist_mean", "family": "h", "labels": {"stage": "a"}}
    assert readers.read(mean, before, after, {}) == 3.0
    assert readers.read(mean, after, after, {}) is None  # nothing happened: nothing to read
    assert readers.read({**mean, "family": "none"}, before, after, {}) is None
    out = {"lat": [1, 2, 3], "n": 30, "s": 10.0}
    assert readers.read({"kind": "outcome", "key": "n"}, None, None, out) == 30
    assert readers.read({"kind": "outcome", "key": "absent"}, None, None, out) is None
    assert readers.read({"kind": "percentile", "key": "lat", "q": 50}, None, None, out) == 2
    assert readers.read({"kind": "rate", "num": "n", "den": "s"}, None, None, out) == 3.0


def check_generators() -> None:
    """The benchmark's own murmur3 against the reference's golden value
    (timestamp.test.ts.snap) and the program's pure-Python copy, and the
    vectorised preload fold against `core.merkle`'s per-message fold."""
    import random

    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_to_string, minute_deltas_host)
    from evolu_tpu.core.murmur import murmur3_32
    from perf import gen

    golden = b"1970-01-01T00:00:00.000Z-0000-0000000000000000"
    assert int(gen.murmur3_32_rows(gen._ascii_rows([golden.decode()], 46))[0]) == 4179357717
    rng = random.Random(1)
    for width in (46, 44, 5, 3):
        rows = [bytes(rng.randrange(32, 127) for _ in range(width)) for _ in range(50)]
        got = gen.murmur3_32_rows(gen._ascii_rows([r.decode() for r in rows], width))
        assert got.tolist() == [murmur3_32(r) for r in rows], width
    requests = gen.build_requests(3000, 7, 2**31 + 11, [b"x", b"y"])
    assert sum(len(r.messages) for r in requests) == 3000
    assert requests == gen.build_requests(3000, 7, 2**31 + 11, [b"x", b"y"])
    for r in requests:
        deltas, _ = minute_deltas_host(m.timestamp for m in r.messages)
        assert r.merkle_tree == merkle_tree_to_string(apply_prefix_xors({}, deltas))
        stamps = [m.timestamp for m in r.messages]
        assert stamps == sorted(set(stamps)) and all(len(t) == 46 for t in stamps)


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _listed(folder: str) -> set:
    return {f[:-5] for f in os.listdir(os.path.join(HERE, folder)) if f.endswith(".json")}


def _module(folder: str, name: str) -> bool:
    return os.path.isfile(os.path.join(HERE, folder, f"{name}.py"))


def check_files() -> None:
    manifest = _json("BENCHMARK.json")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in [*configs, *cells, *end_to_end, *per_layer]:
        assert NAME.match(name), f"name outside the allowed characters: {name!r}"
    for m in [*end_to_end.values(), *per_layer.values()]:
        assert UNIT.match(m["unit"]), f"unit outside the allowed characters: {m['unit']!r}"
        assert m["better"] in ("lower", "higher"), m
        for cell in m.get("workloads", []):
            assert cell in cells, f"{m['name']} lists an unknown cell {cell!r}"
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    lines = [e[k] for e in [*configs.values(), *cells.values(), *per_layer.values()]
             for k in ("why", "source", "layer") if k in e] + manifest["command"]
    for text in lines:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, \
            f"not 1 to 200 characters on one line: {text!r}"

    for name, entry in configs.items():
        cfg = _json(entry["file"])
        assert cfg["name"] == name, f"{entry['file']} is not {name}"
        assert _module("drivers", cfg["driver"]), f"no driver perf/drivers/{cfg['driver']}.py"
        assert set(entry["reduced"]) == set(cfg["reduced"]), \
            f"{name}: `reduced` differs between BENCHMARK.json and its file"
        assert "rehearsal" in cfg and "guarantees" in cfg, name
    for name in _listed("configs"):
        assert name in configs, f"perf/configs/{name}.json is in no BENCHMARK.json entry"

    for name, cell in cells.items():
        w = _json("perf", "workloads", f"{name}.json")
        assert (w["name"], w["config"], w["traffic"]) == \
            (name, cell["config"], cell["traffic"]), f"{name}: file and manifest differ"
        assert cell["config"] in configs, f"{name}: unknown configuration"
        assert cell["chips"] == _json(configs[cell["config"]]["file"])["chips"], name
        assert _module("traffic", w["generator"]), \
            f"no generator perf/traffic/{w['generator']}.py"
        reports = [m for m in end_to_end.values() if name in m.get("workloads", [name])]
        assert len(reports) >= 2, f"{name} reports no end-to-end metric besides setup_s"
        assert any(name in m.get("workloads", [name]) for m in per_layer.values()), \
            f"{name} reports no per-layer metric"
    for name in _listed("workloads"):
        assert name in cells, f"perf/workloads/{name}.json is in no BENCHMARK.json entry"

    def check_metric(folder, name, entry):
        spec = _json("perf", folder, f"{name}.json")
        for key in ("name", "unit", "better"):
            assert spec[key] == entry[key], f"{folder}/{name}.json: {key} differs from the manifest"
        kind = spec["read"]["kind"]
        assert kind in readers.KINDS or _module("kinds", kind), f"{name}: unknown kind {kind!r}"
        return spec

    for name, entry in end_to_end.items():
        check_metric("metrics", name, entry)
    for name, entry in per_layer.items():
        spec = check_metric("layers", name, entry)
        assert (spec["layer"], spec["moves"]) == (entry["layer"], entry["moves"]), name
        assert entry["moves"] in end_to_end, f"{name} moves an unknown metric"
        moved = end_to_end[entry["moves"]]
        for cell in entry.get("workloads", list(cells)):
            assert cell in moved.get("workloads", [cell]), \
                f"{name} is reported in {cell}, where {entry['moves']} is not"
            assert cell in spec["cells"] or cells[cell]["config"] in spec["cells"], \
                f"{name}: {cell} is outside the file's `cells`"
    for name in _listed("metrics"):
        assert name in end_to_end, f"perf/metrics/{name}.json is in no BENCHMARK.json entry"
    for name in _listed("layers"):
        assert name in per_layer, f"perf/layers/{name}.json is in no BENCHMARK.json entry"


def main() -> int:
    check_union()
    check_percentile()
    check_readers()
    check_generators()
    check_files()
    print("perf/selfcheck.py: ok", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
