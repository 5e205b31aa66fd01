"""Run one cell of the benchmark once and print its result line.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell's entry in
`BENCHMARK.json`, its traffic parameters in `perf/workloads/<cell>.json`,
its configuration's file (sizes and the name of a driver module under
`perf/drivers/`), the traffic generator under `perf/traffic/`, and one
small file per metric (`perf/metrics/` end to end, `perf/layers/` per
layer) read by `perf/readers.py`. This file holds no cell's,
configuration's or metric's name except `setup_s`, which it measures.

Set-up (data from the seed, preload, compilation, warm-up) runs from
process start to the start of the window and is reported as `setup_s`;
then the traffic runs for `--seconds`; then the driver's check decides
`correct`, outside the window. The last line of stdout is the result; every
earlier line is one JSON object of information. Any backend but a TPU is
refused (exit 2, nothing on stdout) unless `--rehearse` is given, which
is for the CPU sandbox only: it takes the `rehearsal` sizes and says
`"platform": "cpu"`, and no number from it is a device's.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perf import load_module, readers  # noqa: E402

SLICE_S = 3.0  # the profiled slice of a --trace 1 window, at most


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"perf/run.py: no {what} named {name!r} in BENCHMARK.json")


def metrics_of(entries, cell: str) -> list:
    """The manifest's metrics that this cell reports."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


class CompileLog:
    """Counts XLA backend compiles through `jax.monitoring` (copied from
    chip_smoke.py): a program read back from the persistent cache still
    counts, in the seconds it takes to read it."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.programs = []  # (fun_name, seconds) of every compile >= 1 s
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds
            if seconds >= 1.0:
                self.programs.append((str(kw.get("fun_name")), round(seconds, 1)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self) -> dict:
        return {"compiles": self.compiles, "compile_s": round(self.compile_s, 2),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses,
                "programs_over_1s": self.programs}


class Window:
    """The measured window's edges. The traffic module calls `begin()`
    at `t_start` and `end()` when its last request is answered; each
    takes a reading of the program's metrics registry and of the compile
    count, and a `--trace 1` window profiles a short slice in between."""

    def __init__(self, compiles: CompileLog, trace_dir, seconds: float,
                 allow_host: bool):
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.seconds = seconds
        self.allow_host = allow_host
        self.before = self.after = None
        self.compiles_inside = None
        self.trace = None
        self._thread = None

    def begin(self) -> None:
        from evolu_tpu.obs import metrics

        self.before = metrics.registry.snapshot()
        self._compiles_before = self.compiles.compiles
        if self.trace_dir is not None:
            self._thread = threading.Thread(target=self._slice, daemon=True)
            self._thread.start()

    def end(self) -> None:
        from evolu_tpu.obs import metrics

        if self._thread is not None:
            self._thread.join()
        self.after = metrics.registry.snapshot()
        self.compiles_inside = self.compiles.compiles - self._compiles_before

    def _slice(self) -> None:
        import jax

        from perf import reduce

        length = min(SLICE_S, self.seconds / 2)
        time.sleep(min(1.0, self.seconds / 4))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        t0 = time.monotonic()
        time.sleep(length)
        window_s = time.monotonic() - t0
        jax.profiler.stop_trace()
        self.trace = reduce.read_trace(self.trace_dir, allow_host=self.allow_host)
        self.trace["window_s"] = window_s


def device_report(devices, rehearse: bool) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
        elif not rehearse:
            raise SystemExit("perf/run.py: the device reports no peak_bytes_in_use")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks, default=0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU sandbox only: rehearsal sizes, platform cpu")
    args = ap.parse_args(argv)
    if not __debug__:
        print("perf/run.py checks with assert; run it without -O", file=sys.stderr)
        return 2

    manifest = load_json("BENCHMARK.json")
    cell = by_name(manifest["workloads"], args.workload, "workload")
    config_entry = by_name(manifest["configs"], cell["config"], "config")
    cfg = load_json(config_entry["file"])
    workload = load_json("perf", "workloads", f"{cell['name']}.json")
    params = dict(workload["params"])
    if args.rehearse:
        from perf import selfcheck

        selfcheck.main()
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        params.update(workload.get("rehearsal", {}))

        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if not args.rehearse and jax.default_backend() != "tpu":
        print(f"perf/run.py needs a TPU; JAX came up on {jax.default_backend()!r} "
              "(--rehearse is for the CPU sandbox)", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < cell["chips"]:
        print(f"perf/run.py: {cell['name']} needs {cell['chips']} chip(s), JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    subprocess.run(["make", "-s", "-C", os.path.join(ROOT, "native")],
                   check=True, timeout=600, stdout=sys.stderr)
    import evolu_tpu.ops  # noqa: F401 - places the compile cache
    from evolu_tpu.utils import log as program_log

    # The same threshold whether the cache directory comes from the
    # environment or from the program, so that set-up repeats.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    compiles = CompileLog()
    driver = load_module("drivers", cfg["driver"])
    traffic = load_module("traffic", workload["generator"])
    scratch = tempfile.mkdtemp(prefix="perf-run-")  # under TMPDIR, outside the checkout
    trace_dir = os.path.join(scratch, "trace") if args.trace else None
    if args.trace:
        program_log.enable_trace_annotations()
    emit({"info": "start", "cell": cell["name"], "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace, "rehearse": args.rehearse,
          "jax": jax.__version__, "devices": len(devices),
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})

    state = None
    try:
        state = driver.setup(cfg, args.seed, scratch)
        if hasattr(traffic, "prepare"):
            traffic.prepare(state, params, args.seed)
        driver.warm(state, params)
        emit({"info": "set-up", "seconds": round(time.monotonic() - T0, 3),
              **state.get("timings", {}), **compiles.report()})
        window = Window(compiles, trace_dir, args.seconds, args.rehearse)
        t_start = time.monotonic() + params.get("lead_s", 0.0) + 0.25
        outcome = traffic.run(state, params, args.seed, t_start, args.seconds, window)
        outcome["setup_s"] = t_start - T0
        outcome["window_compiles"] = window.compiles_inside
        device = device_report(devices, args.rehearse)
        try:
            correct = bool(driver.check(state, outcome))
        except AssertionError as e:
            print(f"perf/run.py: check failed: {e}", file=sys.stderr)
            emit({"info": "check failed", "reason": str(e)})
            correct = False
    finally:
        if state is not None:
            if hasattr(traffic, "cleanup"):
                traffic.cleanup(state)
            driver.close(state)
        shutil.rmtree(scratch, ignore_errors=True)

    if args.trace:
        trace = window.trace or {}
        emit({"info": "trace", "layout": trace.get("layout"),
              "planes": trace.get("planes"), "top_ops": trace.get("top_ops")})
        if trace.get("busy_s") is None:
            raise SystemExit("perf/run.py: the trace holds no device plane")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])

    def read_all(entries, folder: str) -> dict:
        values = {}
        for entry in metrics_of(entries, cell["name"]):
            spec = load_json("perf", folder, f"{entry['name']}.json")["read"]
            value = readers.read(spec, window.before, window.after, outcome)
            if value is not None:
                values[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return values

    end_to_end = read_all(manifest["end_to_end"], "metrics")
    per_layer = read_all(manifest["per_layer"], "layers")
    missing = [m["name"] for m in metrics_of(manifest["end_to_end"], cell["name"])
               if m["name"] not in end_to_end]
    if missing:
        raise SystemExit(f"perf/run.py: nothing to read for {missing}")
    # The result line carries one set; the other goes on a line of
    # information, so that every run can be read layer by layer.
    metrics, other = (per_layer, end_to_end) if args.trace else (end_to_end, per_layer)
    emit({"info": "the other set", "metrics": other})
    emit({"info": "outcome", **{k: v for k, v in outcome.items()
                                if isinstance(v, (int, float, str, bool))},
          **compiles.report()})
    emit({"correct": correct, "attempted": outcome["attempted"],
          "failed": outcome["failed"], "metrics": metrics, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
