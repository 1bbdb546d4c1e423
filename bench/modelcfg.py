"""A configuration file (``bench/configs/<name>.json``) and the two views of
it that the benchmark needs: the plain dimensions its own yardstick reads
(reference, FLOP count) and the program's ``ModelConfig``.

The file keeps the source's ``config.json`` keys and values, as run, at its
top level; ``reduced`` names the keys changed from the source.
"""
from __future__ import annotations

import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def dims(cfg: dict) -> dict:
    """The shapes and constants of the published architecture."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d_model": d,
        "n_heads": h,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or d // h,
        "d_ff": cfg["intermediate_size"],
        "n_layers": cfg["num_hidden_layers"],
        "vocab_size": cfg["vocab_size"],
        "tied": bool(cfg["tie_word_embeddings"]),
        "qkv_bias": bool(cfg["attention_bias"]),
        "eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
    }


def program_config(cfg: dict):
    """The program's ``ModelConfig``: its registry entry (``registry``) with
    every dimension of the file laid over it, so the program runs exactly the
    file's configuration."""
    from repro.configs import get_config

    d = dims(cfg)
    return get_config(cfg["registry"]).with_(
        n_layers=d["n_layers"], d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], qkv_bias=d["qkv_bias"],
        tie_embeddings=d["tied"], norm_eps=d["eps"],
        rope_theta=d["rope_theta"], param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"])
