"""The benchmark of record: see perf/README.md."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(folder: str, name: str):
    """`perf/<folder>/<name>.py`, found by the name a data file gives."""
    path = os.path.join(HERE, folder, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perf.{folder}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
