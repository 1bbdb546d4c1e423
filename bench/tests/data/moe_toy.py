"""Toy architecture for the harness's self-test: the dense layer with its
SwiGLU feed-forward replaced by a mixture of experts that routes every
token to every expert (``num_experts_per_tok`` = ``num_local_experts``), the
combination weighted by the router's softmax.  With every expert chosen no
near-tie of the router can flip a choice between the program's bfloat16 and
the reference's float32, and the program's capacity (``capacity_factor`` =
experts / experts per token) drops no token."""
import math

import jax
import jax.numpy as jnp

from bench.arch import dense
from bench.reference import mm, rms


def dims(cfg: dict) -> dict:
    return {**dense.dims(cfg), "n_experts": cfg["num_local_experts"],
            "top_k": cfg["num_experts_per_tok"]}


def layer_specs(d: dict):
    D, F, E = d["d_model"], d["d_ff"], d["n_experts"]
    return dense.attn_specs(d) + [
        ("moe", "router", (D, E), 1 / math.sqrt(D), 0.0),
        ("moe", "w_gate", (E, D, F), 1 / math.sqrt(D), 0.0),
        ("moe", "w_up", (E, D, F), 1 / math.sqrt(D), 0.0),
        ("moe", "w_down", (E, F, D), 1 / math.sqrt(F), 0.0),
    ] + dense.bias_specs(d)


def block(w, x, d: dict, quant: bool):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = dense.attention_half(w, x, d, quant)
    a = rms(x, w["ffn_norm"], d["eps"])
    m = w["moe"]
    probs = jax.nn.softmax(mm(a, m["router"], quant), axis=-1)     # (T, E)
    out = 0.0
    for e in range(d["n_experts"]):
        f = {"w_gate": m["w_gate"][e], "w_up": m["w_up"][e], "w_down": m["w_down"][e]}
        out = out + probs[:, e:e + 1] * dense.swiglu(f, a, quant)
    return x + out


def matmul_params_per_layer(d: dict) -> int:
    D = d["d_model"]
    return (dense.attn_matmul_params(d) + d["top_k"] * 3 * D * d["d_ff"]
            + D * d["n_experts"])


def program_overrides(d: dict) -> dict:
    from repro.configs.base import MoEConfig

    return {**dense.program_overrides(d), "moe_impl": "scatter", "moe": MoEConfig(
        n_experts=d["n_experts"], top_k=d["top_k"], d_ff=d["d_ff"],
        capacity_factor=d["n_experts"] / d["top_k"])}
