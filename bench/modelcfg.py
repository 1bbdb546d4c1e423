"""A configuration file (``bench/configs/<name>.json``) and the two views of
it that the benchmark needs: the plain dimensions its own yardstick reads
(reference, FLOP count) and the program's ``ModelConfig``.

The file keeps the source's ``config.json`` keys and values, as run, at its
top level; ``reduced`` names the keys changed from the source, and
``architecture`` the file under ``bench/arch`` that reads them (``dense``
where the key is absent).
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import arch

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def dims(cfg: dict) -> dict:
    """The shapes and constants of the published architecture, as the file's
    architecture (``bench/arch``) reads them, with its name."""
    name = cfg.get("architecture", arch.DEFAULT)
    return {**arch.load(name).dims(cfg), "architecture": name}


def program_config(cfg: dict):
    """The program's ``ModelConfig``: its registry entry (``registry``) with
    every dimension of the file laid over it, so the program runs exactly the
    file's configuration."""
    from repro.configs import get_config

    d = dims(cfg)
    return get_config(cfg["registry"]).with_(
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        **arch.of(d).program_overrides(d))
