"""Device (JAX/TPU) kernels for the CRDT hot paths.

These kernels replace the reference's per-message loops (reference:
packages/evolu/src/applyMessages.ts:78, merkleTree.ts:31-50) with
columnar batch pipelines:

- `hash`    — vmapped murmur3-32 over fixed-width timestamp strings.
- `encode`  — on-device canonical timestamp rendering + packed sort keys.
- `merge`   — radix-style sort + segmented prefix-max LWW planner.
- `merkle_ops` — batched minute-key XOR deltas.

HLC millis are 48-bit, so the kernels need 64-bit integer types. Public
entry points enter `jax.enable_x64` per call (see
`with_x64`) instead of flipping the process-global x64 flag — importing
this package must not change dtype semantics for the host application's
own JAX code. Pass numpy arrays across the host↔device boundary; the
wrappers convert inside the x64 scope so 64-bit dtypes survive.
"""

import functools
import os

import jax

# Kernels compile once per power-of-two bucket, and on the TPU the
# planner programs cost about a minute each (PERF.md, PR 21), so a process
# that starts cold spends its time compiling. JAX's persistent
# compilation cache makes that a per-machine cost. Its directory is
# placed from outside: where JAX_COMPILATION_CACHE_DIR is set (or the
# embedder configured one) this code sets nothing; otherwise it is ONE
# fixed path inside the checkout — the path is part of the cache key,
# so a home, temp, pid or time-derived name would never hit again.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
if (
    "JAX_COMPILATION_CACHE_DIR" not in os.environ
    and jax.config.jax_compilation_cache_dir is None
):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def with_x64(fn):
    """Run `fn` under the `jax.enable_x64(True)` config scope.

    Applied to every public kernel entry point: tracing (and jit cache
    keying) happens under the x64 config, so 64-bit HLC keys keep their
    width regardless of the embedding application's global setting.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)

    return wrapper


def to_host(x):
    """Device array → numpy for 1-D kernel outputs.

    On a multi-device sharded array, `np.asarray` builds and runs an
    XLA gather program per call (~10× slower than the raw copies); this
    instead copies each addressable shard and concatenates in index
    order. Falls back to `np.asarray` for anything that isn't a plain
    axis-0-sharded 1-D array (replicated outputs, numpy inputs)."""
    import numpy as np

    shards = getattr(x, "addressable_shards", None)
    if not shards or len(shards) <= 1 or getattr(x, "ndim", 0) != 1:
        return np.asarray(x)
    pairs = [(s.index[0].start or 0, s.data) for s in shards]
    if len({p[0] for p in pairs}) != len(pairs):  # replicated, not sharded
        return np.asarray(x)
    pairs.sort(key=lambda p: p[0])
    return np.concatenate([np.asarray(d) for _, d in pairs])


def to_host_many(*xs):
    """Batched device→host pull: start EVERY copy asynchronously first,
    then materialize — one transfer wave for a kernel's 7-9 outputs
    instead of one blocking pull per array (what a per-array pull costs
    on the attached chip: not measured). Per-array conversion still
    goes through `to_host` (sharded-aware). Returns a tuple in input
    order; numpy inputs pass through.

    Instrumented (ISSUE 15 satellite): pull bytes / wave-size histogram
    / pull-seconds counter give the device→host leg as a live
    gauge-derived MB/s. The instrumentation is HOST-side, after the
    pull materialized — it reads `.nbytes` off the returned numpy
    arrays, never touches the traced graph, and costs a few dict ops
    per WAVE (checksum + jit-cache invariance pinned by
    tests/test_bench_liveness.py)."""
    from evolu_tpu.obs import anatomy as _anatomy
    from evolu_tpu.obs import metrics as _metrics

    # Stage-anatomy fold (ISSUE 16): every wave is one pull_wave stage
    # record (and, with annotations on, one `evolu/pull_wave` event on
    # the pulling thread's line of the profiler trace); where the
    # device has a recorded pull bandwidth law, the over-floor flag
    # fires when a wave runs slower than FLOOR_FACTOR× it.
    with _anatomy.stage("pull_wave", cpu=False) as wave:  # nothing reads its wait
        out = tuple(to_host(x) for x in start_host_transfer(*xs))
        wave.nbytes = sum(int(getattr(a, "nbytes", 0)) for a in out)
    if _metrics.registry.enabled:
        _metrics.inc("evolu_pull_bytes_total", wave.nbytes)
        _metrics.inc("evolu_pull_seconds_total", wave.seconds)
        _metrics.observe("evolu_pull_wave_bytes", wave.nbytes,
                         buckets=_metrics.SIZE_BUCKETS)
    return out


def start_host_transfer(*xs):
    """The async-start half of `to_host_many`, for pipelining: begin
    every device→host copy NOW and return the arrays untouched; a later
    `to_host_many` on them materializes mostly-finished copies."""
    for x in xs:
        shards = getattr(x, "addressable_shards", None)
        if shards:
            for s in shards:
                try:
                    s.data.copy_to_host_async()
                except AttributeError:
                    break
        else:
            try:
                x.copy_to_host_async()
            except AttributeError:
                pass
    return xs


def bucket_size(n: int, multiple: int = 64) -> int:
    """Power-of-two batch bucket ≥ max(n, multiple). One policy for
    every host→device batch (SURVEY.md §7 "dynamic shapes": pad to
    pow2 buckets so jit compiles once per bucket, not per batch)."""
    size = multiple
    while size < n:
        size *= 2
    return size
