"""Test env: force JAX onto a virtual 8-device CPU mesh.

The tests run on the CPU backend with 8 virtual host devices, set
before any jax backend initializes; sharding tests validate mesh
semantics on the virtual mesh. What only the chip can show is compiled
for it in tests/test_tpu_compile.py (a described topology, nothing
runs) and run on it by chip_smoke.py.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# The byte-identity parity dump every end-state gate shares
# (test_mesh_engine, test_model_check's oracle-twin episodes): ONE copy,
# kept with the chip smoke that also decides on it.
from chip_smoke import store_dump as relay_store_dump  # noqa: E402,F401
