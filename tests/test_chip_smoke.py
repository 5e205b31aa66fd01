"""chip_smoke.py, rehearsed tiny on the CPU so the script cannot rot
between chip runs: every phase function runs in-process at a size that
takes seconds — the same entry points, the same host references, the
same no-fallback assertions — and the four-chip phases run on the
virtual mesh. What only the chip can show (the Pallas scan route, the
device name in the last line) lives in `main()`, which must refuse to
print a result here.
"""

import json

import pytest

import chip_smoke
from evolu_tpu.obs import metrics

SEED = 5


@pytest.fixture(scope="module")
def compiles():
    # The smoke asserts on process-cumulative counters (its own process
    # is fresh); here earlier files on the same xdist worker have
    # legitimately moved the fallback counters.
    metrics.reset()
    return chip_smoke.CompileLog()


_PHASES = {
    "relay": lambda c: chip_smoke.relay_phase(
        SEED, c, n_messages=4096, owners=16, batches=4, push=8, sample=4),
    "client": lambda c: chip_smoke.client_phase(
        SEED, c, n_messages=4000, rows=40, batches=4),
    "kernel": lambda c: chip_smoke.kernel_phase(SEED, c, rows=4096, owners=16),
    "mesh_relay_4_devices": lambda c: chip_smoke.mesh_phase(
        SEED, c, n_messages=4096, owners=16, n_devices=4),
    "mesh_client": lambda c: chip_smoke.client_phase(
        SEED, c, n_messages=4000, rows=40, batches=4, mesh_engine=True),
    "kernel_4_devices": lambda c: chip_smoke.kernel_phase(
        SEED, c, rows=4096, owners=16, n_devices=4),
}


@pytest.mark.parametrize("phase", sorted(_PHASES))
def test_phase_passes_its_host_reference(compiles, phase):
    report = _PHASES[phase](compiles)
    json.dumps(report)  # one JSON line per phase
    assert not any(report["host_fallbacks"].values())
    assert report["compile"]["compiles"] >= 0
    if phase == "relay":
        assert report["storage_backend"] == "CppSqliteDatabase"
        assert report["checked"]["stored_rows"] == report["messages"] + 8
        assert report["http"]["cold_sync_messages"] > 8
    if phase in ("client", "mesh_client"):
        # The adaptive gate's whole walk, in four batches: streamed,
        # streamed, seeded into HBM, planned from HBM.
        assert [b["mode"] for b in report["batches"]] == \
            ["stream", "stream", "cached", "cached"]
        assert report["batches"][2]["seeded"] > 0
        assert report["batches"][3]["hits"] > 0
        assert report["checked"]["winner_cache_slots_vs_sqlite_max"] > 0
        assert report["winner_cache_class"] == (
            "MeshShardedWinnerCache" if phase == "mesh_client"
            else "DeviceWinnerCache")
    if phase == "mesh_relay_4_devices":
        assert len({s["device"] for s in report["output_shards"]}) == 4


def test_main_refuses_the_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no phase line, and never the "ok" line
    assert "needs a TPU" in out.err


def test_route_and_fallback_assertions_fail_loudly(compiles):
    obs = chip_smoke.observed()
    chip_smoke.assert_no_fallback(obs)
    with pytest.raises(AssertionError, match="fallback counters moved"):
        chip_smoke.assert_no_fallback(
            {"host_fallbacks": {**obs["host_fallbacks"], "merge": 1}})
    chip_smoke.assert_pallas_route({"scan_route": {"path=pallas": 3}})
    for route in ({}, {"path=xla": 1, "path=pallas": 3}, {"path=xla": 2}):
        with pytest.raises(AssertionError, match="Pallas scan route"):
            chip_smoke.assert_pallas_route({"scan_route": route})
