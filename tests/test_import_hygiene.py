"""Mechanical guard for the module-level-jnp-constant invariant.

CLAUDE.md: a concrete jnp array created at import time initializes the
XLA backend and breaks `jax.distributed.initialize` (the multi-host
join must run before any backend touch). Until now the rule lived in
comments; this test enforces it for EVERY `evolu_tpu` module — current
and future (including the jax-free `obs/` package) — by importing each
one in a subprocess whose jax backend is stubbed out: `JAX_PLATFORMS`
names a platform that does not exist, so the import itself succeeds
(jax import never touches a backend) but ANY import-time concrete
array / device lookup raises. A module that imports cleanly there is
proven backend-free at import.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import importlib, json, pkgutil
import evolu_tpu

names = sorted(
    {"evolu_tpu"}
    | {m.name for m in pkgutil.walk_packages(evolu_tpu.__path__, "evolu_tpu.")}
)
bad = {}
for name in names:
    try:
        importlib.import_module(name)
    except Exception as e:  # noqa: BLE001 - report every offender at once
        bad[name] = f"{type(e).__name__}: {e}"
print("RESULT:" + json.dumps(bad))
"""


def test_no_module_initializes_the_xla_backend_at_import():
    env = dict(os.environ)
    # A platform that cannot exist: backend init raises, import machinery
    # does not.
    env["JAX_PLATFORMS"] = "evolu_import_guard_no_such_platform"
    env["PYTHONPATH"] = _REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, f"guard subprocess died:\n{proc.stderr[-3000:]}"
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    bad = json.loads(line[len("RESULT:"):])
    assert bad == {}, (
        "modules touch the XLA backend at import time (module-level jnp "
        f"constant or device lookup — breaks jax.distributed.initialize): {bad}"
    )


def test_obs_package_never_imports_jax():
    """The observability package records host-side Python values only;
    the cheap mechanical proxy is that importing it (alone) must not
    pull jax into the process at all. (The package import covers
    obs.trace too — it is re-exported from obs/__init__.py.)"""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; import evolu_tpu.obs; "
         "print('JAX_LOADED' if 'jax' in sys.modules else 'CLEAN')"],
        env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout, "evolu_tpu.obs transitively imported jax"


def test_anatomy_module_never_imports_jax_and_records_without_a_backend():
    """The explicit pin for the stage module ALONE: importing, setting
    the device kind, recording stages and rendering the /stats payload
    must never pull jax into the process — stages are recorded on relays
    that serve pure-host workloads and must stay jax-free (the device
    kind is PUSHED in from parallel/mesh.py on jax-side paths)."""
    script = (
        "import sys; from evolu_tpu.obs import anatomy\n"
        "anatomy.set_device_kind('TPU v5 lite')\n"
        "anatomy.record_stage('host_apply', 0.01, rows=7200)\n"
        "with anatomy.stage('pass_pack'): pass\n"
        "p = anatomy.stages_payload(); "
        "assert p['stages']['pass_pack']['count'] == 1; "
        "assert p['stages']['host_apply']['count'] == 1; "
        "print('JAX_LOADED' if 'jax' in sys.modules else 'CLEAN')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout, "evolu_tpu.obs.anatomy transitively imported jax"


def test_trace_module_never_imports_jax_and_never_touches_a_backend():
    """ISSUE 10's explicit pin for the tracing module ALONE (not just
    via the package import): importing, minting spans, parsing and
    formatting headers, and exporting must neither pull jax into the
    process nor touch any backend — tracing runs on relays that never
    load jax at all."""
    script = (
        "import sys; from evolu_tpu.obs import trace; "
        "s = trace.start_span('t', attrs={'k': 1}); "
        "ctx = s.context; s.end(); "
        "assert trace.parse_traceparent(trace.format_traceparent(ctx)); "
        "trace.serve_trace(ctx.trace_id); trace.export_chrome(); "
        "print('JAX_LOADED' if 'jax' in sys.modules else 'CLEAN')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout, "evolu_tpu.obs.trace transitively imported jax"


# server/ in four boxes whose arrows point one way:
# store <- engine <- scheduler <- relay (CLAUDE.md). Read from the
# SOURCE, function bodies included: `evolu_tpu/server/__init__.py`
# imports `relay` eagerly, so sys.modules cannot tell who asked for it.
_LAYERS = {
    "store": {"relay", "scheduler", "engine", "conn", "push", "fleet",
              "replicate", "snapshot", "http.server", "jax"},
    "engine": {"relay", "scheduler"},
    "scheduler": {"relay"},
}


def _imported_names(path):
    """Every module a file names in an `import` / `from ... import`, at
    any depth, as dotted names (`from evolu_tpu.server import scope as
    s` yields both `evolu_tpu.server` and `evolu_tpu.server.scope`)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}:{node.lineno}: relative import"
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


@pytest.mark.parametrize("module", sorted(_LAYERS))
def test_server_layers_import_only_what_is_below_them(module):
    path = os.path.join(_REPO, "evolu_tpu", "server", module + ".py")
    names = _imported_names(path)
    assert any(n.startswith("evolu_tpu.") for n in names), names
    bad = sorted(
        n for n in names for b in _LAYERS[module]
        if n in (b, "evolu_tpu.server." + b) or n.startswith(b + ".")
    )
    assert bad == [], f"server/{module}.py reaches above or beside itself: {bad}"
