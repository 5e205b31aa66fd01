"""From a profiler trace to the device's busy seconds.

`busy_seconds` is a pure function over intervals (checked by
`perf/selfcheck.py` on hand-made ones); `read_trace` is the thin reader
that pulls those intervals out of the `.xplane.pb` the JAX profiler wrote.
Busy is the union of the intervals in which an operation ran on the
device, averaged over the device planes; the idle share is 1 - busy /
window, which the driver works out from `busy_s` and `window_s`.

Which events count: on a plane named `/device:TPU:<n>` the line
`XLA Ops` holds one event per executed HLO operation (the line
`XLA Modules` holds one per program run and is the fallback where a
trace has no op line). `Steps` and the TraceMe lines are markers, not
work. With `allow_host=True` (the CPU rehearsal only, where there is no
device plane) the XLA client threads of `/host:CPU` stand in, so that
the rehearsal exercises this code; such a number is never a device's.
"""

import glob
import os

from perf.stats import union_seconds

DEVICE_PLANE = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")
HOST_STAND_INS = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")


def busy_seconds(intervals_ns) -> float:
    """Union length, in seconds, of (start_ns, end_ns) intervals."""
    intervals = list(intervals_ns)
    if not intervals:
        return 0.0
    lo = min(s for s, _e in intervals)
    hi = max(e for _s, e in intervals)
    return union_seconds(intervals, lo, hi) / 1e9


def _line_intervals(line):
    return [(e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events if e.duration_ns > 0]


def _plane_intervals(plane, allow_host: bool):
    """The busy intervals of one plane, or None where it is no device."""
    lines = {line.name: line for line in plane.lines}
    if plane.name.startswith(DEVICE_PLANE):
        for name in OP_LINES:
            if name in lines:
                return _line_intervals(lines[name])
        return []
    if allow_host and plane.name == "/host:CPU":
        out = []
        for name, line in lines.items():
            if name.startswith(HOST_STAND_INS):
                out.extend(_line_intervals(line))
        return out
    return None


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_trace(trace_dir: str, allow_host: bool = False) -> dict:
    """→ {"busy_s": mean over device planes, "planes": per-plane busy,
    "layout": planes and lines with event counts, "top_ops": the ten op
    names with most summed device time}. `busy_s` is None where the
    trace holds no device plane."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return {"busy_s": None, "planes": {}, "layout": [], "top_ops": []}
    data = ProfileData.from_file(path)
    layout, per_plane, op_seconds = [], {}, {}
    for plane in data.planes:
        lines = list(plane.lines)
        layout.append({"plane": plane.name, "lines": [
            [line.name, sum(1 for _ in line.events)] for line in lines[:64]]})
        intervals = _plane_intervals(plane, allow_host)
        if intervals is None:
            continue
        per_plane[plane.name] = busy_seconds(intervals)
        if plane.name.startswith(DEVICE_PLANE):
            for line in lines:
                if line.name == OP_LINES[0]:
                    for e in line.events:
                        op_seconds[e.name] = op_seconds.get(e.name, 0.0) + e.duration_ns / 1e9
    busy = sum(per_plane.values()) / len(per_plane) if per_plane else None
    top = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy, "planes": per_plane, "layout": layout,
            "top_ops": [[name, seconds] for name, seconds in top]}
