"""Architecture modules: everything of the benchmark that depends on a
configuration's encoder block, one file per architecture.

A configuration file names its module by its ``"arch"`` key:
``bench/arch/<arch>.py``.  The module states

- ``init_weights(cfg, seed)``: the plain reference's float32 weights,
  remade from the seed;
- ``forward(cfg)``: the reference, a jitted
  ``(weights, tokens (B, S), mask (B, S)) -> (B, d)`` unit vectors;
- ``encoder_flops(length, cfg)``: the matmul operations one sequence of
  ``length`` real tokens requires;
- ``program_fields(cfg)``: program configuration attribute -> the value
  the file states, checked on the served program before any traffic;
- ``seeded_init(init, std)``: the program's initialisation ``init`` with
  the token table drawn as the reference draws it, and ``PROGRAM_INIT``,
  the dotted path of the program's function that it wraps;
- ``SMALL``: the configuration keys of its cut for the CPU tests, at
  least one whole period of its layer pattern.

``bench/run.py`` loads the module of the checkout it runs from
(``load``); ``bench/reference.py`` and ``bench/flops.py`` look it up by
the configuration alone (``of``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[2]
# architecture name -> its module, as last loaded in this process
_loaded: dict = {}


def load(root: Path, name: str) -> ModuleType:
    """``bench/arch/<name>.py`` of the checkout at ``root``, which every
    later ``of`` of that name in this process returns."""
    path = Path(root) / "bench" / "arch" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.arch.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _loaded[name] = mod
    return mod


def of(cfg: dict) -> ModuleType:
    """The architecture module that the configuration names."""
    name = cfg["arch"]
    return _loaded.get(name) or load(ROOT, name)
