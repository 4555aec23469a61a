"""Find a served model's backend module by its architecture.

A configuration's ``backend.architecture`` names a module
``bench/backends/<architecture>.py``; its interface is written at the
top of ``bench/backends/dense.py``.  Everything that knows a layer of a
served model lives in that module, so a new architecture comes as a new
file and nothing else here changes.
"""
from __future__ import annotations

import importlib.util
import pathlib
from types import ModuleType
from typing import Dict

DIR = pathlib.Path(__file__).resolve().parents[1] / "backends"

_loaded: Dict[pathlib.Path, ModuleType] = {}


class UnknownArchitecture(LookupError):
    pass


def import_file(path: pathlib.Path, prefix: str) -> ModuleType:
    """Import the Python file at ``path`` as a module of its own, named
    ``prefix`` and the file's stem (a backend here, a metric's reader in
    ``harness.load_reader``)."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(architecture: str) -> ModuleType:
    """The backend module, imported once: its jitted functions keep
    their compiled programs across the runs of one process."""
    path = DIR / f"{architecture}.py"
    mod = _loaded.get(path)
    if mod is not None:
        return mod
    if not path.is_file():
        raise UnknownArchitecture(
            f"no backend for architecture {architecture!r}: {path} "
            f"does not exist")
    mod = _loaded[path] = import_file(path, "bench_backend_")
    return mod


def of(b: dict) -> ModuleType:
    """The module of a configuration's ``backend`` group."""
    return load(b["architecture"])
