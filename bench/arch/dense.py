"""Dense decoder layer, as in Hugging Face's Qwen2 and Mistral: RMSNorm,
rotary embeddings with the half-split rotation, grouped-query causal
attention with optional QKV biases, and a SwiGLU feed-forward."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import attention, mm, rms, rope


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


def attn_specs(d: dict):
    """(group, name, shape, std, mean) of the two norms and the attention
    projections, the leaves every layer of this family opens with."""
    D, H, K, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    return [
        (None, "attn_norm", (D,), 0.1, 1.0),
        ("attn", "wq", (D, H, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wk", (D, K, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wv", (D, K, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wo", (H, hd, D), 1 / math.sqrt(H * hd), 0.0),
        (None, "ffn_norm", (D,), 0.1, 1.0),
    ]


def bias_specs(d: dict):
    """The QKV biases, drawn after the layer's other leaves."""
    H, K, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    if not d["qkv_bias"]:
        return []
    return [("attn", "bq", (H, hd), 0.2, 0.0),
            ("attn", "bk", (K, hd), 0.2, 0.0),
            ("attn", "bv", (K, hd), 0.2, 0.0)]


def layer_specs(d: dict):
    """(group, name, shape, std, mean) of one layer's leaves."""
    D, F = d["d_model"], d["d_ff"]
    return attn_specs(d) + [
        ("ffn", "w_gate", (D, F), 1 / math.sqrt(D), 0.0),
        ("ffn", "w_up", (D, F), 1 / math.sqrt(D), 0.0),
        ("ffn", "w_down", (F, D), 1 / math.sqrt(F), 0.0),
    ] + bias_specs(d)


def attention_half(w, x, d: dict, quant: bool):
    """The residual stream after the layer's attention (float32 ``w``)."""
    T, D = x.shape
    H, K, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    a = rms(x, w["attn_norm"], d["eps"])
    at = w["attn"]
    q = mm(a, at["wq"].reshape(D, H * hd), quant).reshape(T, H, hd)
    k = mm(a, at["wk"].reshape(D, K * hd), quant).reshape(T, K, hd)
    v = mm(a, at["wv"].reshape(D, K * hd), quant).reshape(T, K, hd)
    if "bq" in at:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    o = attention(q, k, v).reshape(T, H * hd)
    return x + mm(o, at["wo"].reshape(H * hd, D), quant)


def swiglu(f, a, quant: bool):
    h = jax.nn.silu(mm(a, f["w_gate"], quant)) * mm(a, f["w_up"], quant)
    return mm(h, f["w_down"], quant)


def block(w, x, d: dict, quant: bool):
    """One layer over a whole sequence ``x`` (T, D), in float32."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = attention_half(w, x, d, quant)
    a = rms(x, w["ffn_norm"], d["eps"])
    return x + swiglu(w["ffn"], a, quant)


def attn_matmul_params(d: dict) -> int:
    D, hd = d["d_model"], d["head_dim"]
    return D * d["n_heads"] * hd + 2 * D * d["n_kv_heads"] * hd + d["n_heads"] * hd * D


def matmul_params_per_layer(d: dict) -> int:
    return attn_matmul_params(d) + 3 * d["d_model"] * d["d_ff"]


def program_overrides(d: dict) -> dict:
    """Every dimension of the file, as the program's ``ModelConfig`` names
    it."""
    return dict(
        n_layers=d["n_layers"], d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], qkv_bias=d["qkv_bias"],
        tie_embeddings=d["tied"], norm_eps=d["eps"],
        rope_theta=d["rope_theta"])
