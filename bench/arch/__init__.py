"""Architectures, one file each: ``bench/arch/<name>.py``, named by a
configuration file's ``"architecture"`` key (``dense`` where it names none).

An architecture file holds what the benchmark's yardstick knows of one kind
of block, and nothing of the program:

- ``dims(cfg)``: the plain dimensions the weights, the reference and the
  FLOP count read, from the configuration file's keys;
- ``layer_specs(d)``: ``(group, name, shape, std, mean)`` of one layer's
  leaves, in the layout the program takes (``bench.weights`` draws them);
- ``block(w, x, d, quant)``: one float32 reference layer over a sequence
  (``bench.reference``, whose shared pieces it may use);
- ``matmul_params_per_layer(d)``: weight-matmul parameters one token
  passes through in one layer (``bench.flops``);
- ``program_overrides(d)``: the program's ``ModelConfig`` fields that make
  it run exactly these dimensions (``bench.modelcfg``).

The embedding, the unembedding, attention FLOPs and the blocked float32
scoring are shared and live in those modules.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Dict, List

DEFAULT = "dense"
# searched in order; a test puts its own directory first
ARCH_DIRS: List[Path] = [Path(__file__).resolve().parent]
_LOADED: Dict[str, ModuleType] = {}     # by name: FLOP counts look up per call


def load(name: str) -> ModuleType:
    """The architecture file ``<name>.py`` of the first directory that has
    one, loaded once a process."""
    mod = _LOADED.get(name)
    if mod is not None:
        return mod
    for base in ARCH_DIRS:
        path = base / f"{name}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _LOADED[name] = mod
            return mod
    raise SystemExit(f"bench: no architecture {name!r} in "
                     f"{', '.join(str(p) for p in ARCH_DIRS)}")


def of(d: dict) -> ModuleType:
    """The architecture of a configuration file or of its ``dims``."""
    return load(d.get("architecture", DEFAULT))
