"""Files of the benchmark found by name.

Everything that belongs to one item sits in a file of its own, which the
harness finds by the name that ``BENCHMARK.json`` or a configuration or
traffic file gives: ``builders/<builder>.py`` and
``references/<reference>.py`` (a configuration's ``builder`` and
``reference``), ``arrivals/<kind>.py`` (a mix's ``arrivals.kind``),
``metrics/<metric>.py`` and ``kernels/<kernel>.py``.  Adding one is adding
a file.
"""
from __future__ import annotations

import importlib.util
import sys
from functools import lru_cache
from pathlib import Path

HERE = Path(__file__).resolve().parent


@lru_cache(maxsize=None)
def module(kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded once."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod
