"""Metric readers: one function per `kind`, configured by data.

A metric's file (`perf/metrics/<name>.json` end to end,
`perf/layers/<name>.json` per layer) holds `"read": {"kind": …}`. Every
reader takes (spec, before, after, outcome): two
`obs.metrics.registry.snapshot()` readings taken at the window's edges
and the traffic module's outcome. A reader that finds nothing to read
returns None and the harness leaves the metric out of the line. A kind
that is not here is looked up as `perf/kinds/<kind>.py::read`.
"""

from perf import load_module
from perf.stats import percentile


def _series(snapshot, section, family, labels):
    for entry in snapshot[section].get(family, []):
        if entry["labels"] == labels:
            return entry
    return None


def _counter_delta(before, after, family, labels):
    """Sum over every series of the family whose labels include
    `labels` (an empty dict is the whole family)."""
    def total(snap):
        return sum(
            e["value"] for e in snap["counters"].get(family, [])
            if all(e["labels"].get(k) == v for k, v in labels.items()))
    return total(after) - total(before)


def counter_ratio(spec, before, after, outcome):
    """scale × Σ Δnum / Σ Δden; `num` and `den` are lists of
    [family, labels] terms."""
    num = sum(_counter_delta(before, after, f, l) for f, l in spec["num"])
    den = sum(_counter_delta(before, after, f, l) for f, l in spec["den"])
    if den <= 0:
        return None
    return spec.get("scale", 1.0) * num / den


def hist_mean(spec, before, after, outcome):
    """Δsum / Δcount of one histogram series."""
    b = _series(before, "histograms", spec["family"], spec.get("labels", {}))
    a = _series(after, "histograms", spec["family"], spec.get("labels", {}))
    if a is None:
        return None
    count = a["count"] - (b["count"] if b else 0)
    if count <= 0:
        return None
    return spec.get("scale", 1.0) * (a["sum"] - (b["sum"] if b else 0.0)) / count


def outcome_value(spec, before, after, outcome):
    """A number the harness or the traffic module measured itself."""
    value = outcome.get(spec["key"])
    return None if value is None else spec.get("scale", 1.0) * value


def outcome_percentile(spec, before, after, outcome):
    """The q-th percentile over ALL samples of an outcome list."""
    samples = outcome.get(spec["key"])
    if not samples:
        return None
    return spec.get("scale", 1.0) * percentile(samples, spec["q"])


def outcome_rate(spec, before, after, outcome):
    """outcome[num] / outcome[den]: all the work over all the time."""
    num, den = outcome.get(spec["num"]), outcome.get(spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


KINDS = {
    "counter_ratio": counter_ratio,
    "hist_mean": hist_mean,
    "outcome": outcome_value,
    "percentile": outcome_percentile,
    "rate": outcome_rate,
}


def read(spec: dict, before, after, outcome):
    kind = spec["kind"]
    reader = KINDS.get(kind) or load_module("kinds", kind).read
    return reader(spec, before, after, outcome)
