"""What the program counted: routes and fallbacks, read from its metrics
registry (copied from chip_smoke.py's `counters`, `total`, `observed`,
`assert_no_fallback`, `assert_pallas_route`). The drivers' checks use
them: a cell is not correct if a hidden host path did the work."""


def counters(name: str) -> dict:
    """{label-string: value} of one counter family."""
    from evolu_tpu.obs import metrics

    fam = metrics.registry.snapshot()["counters"].get(name, [])
    return {
        ",".join(f"{k}={v}" for k, v in sorted(e["labels"].items())): e["value"]
        for e in fam
    }


def total(name: str) -> float:
    return sum(counters(name).values())


def fallbacks() -> dict:
    return {
        "merge": total("evolu_merge_host_fallbacks_total"),
        "winner_cache": total("evolu_winner_cache_host_fallbacks_total"),
        "reconcile_owners": total("evolu_reconcile_host_owner_fallbacks_total"),
        "packed_bounces": total("evolu_apply_packed_bounces_total"),
        "native_load_failures": total("evolu_native_load_failures_total"),
        "sched_fallback": total("evolu_sched_fallback_total"),
        "sched_poisoned": total("evolu_sched_poisoned_batches_total"),
        "sched_rejected": total("evolu_sched_rejected_total"),
    }


def assert_no_fallback() -> None:
    """The data is canonical and the native libraries are built from
    source: any fallback counter that moved is a hidden device or
    host-path failure."""
    moved = {k: v for k, v in fallbacks().items() if v}
    assert not moved, f"fallback counters moved: {moved}"


def assert_pallas_route() -> None:
    """On a TPU every scan traced at N >= 2^15 must have taken the
    Pallas kernel (`evolu_merge_scan_total` counts only where there is a
    choice). On any other backend (the rehearsal) there is no claim."""
    import jax

    if jax.default_backend() != "tpu":
        return
    route = counters("evolu_merge_scan_total")
    assert not route.get("path=xla", 0) and route.get("path=pallas", 0), \
        f"Pallas scan route not taken at N >= 2^15: {route}"


def assert_native() -> None:
    from evolu_tpu.storage import native
    from evolu_tpu.sync import native_crypto

    assert native.native_available(), "libevolu_host.so did not load"
    assert native_crypto.native_available(), "libevolu_crypto.so did not load"
