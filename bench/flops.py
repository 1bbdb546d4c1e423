"""Model FLOPs a round of chunked prefill and decode needs, from the
configuration's shapes alone.

Counted: every weight matmul of every scheduled token (the parameters the
architecture's ``matmul_params_per_layer`` says one token passes through),
causal attention over each token's real context (QK^T and PV), and the
unembedding of sampled positions only.  Not counted: padding rows and lanes, gathers, norms,
elementwise work — so the count reads the same whatever implements the step.
"""
from __future__ import annotations

from bench import arch


def chunk_flops(cfg: dict, start: int, n: int, sampled: bool) -> int:
    """FLOPs of ``n`` tokens at positions ``start .. start + n - 1`` of one
    sequence, plus one unembedding when the chunk samples a token."""
    L = cfg["n_layers"]
    linear = 2 * arch.of(cfg).matmul_params_per_layer(cfg) * n * L
    # query at position p attends to p + 1 keys: QK^T and PV, 2 FLOPs each
    keys = n * start + n * (n + 1) // 2
    attn = 4 * cfg["n_heads"] * cfg["head_dim"] * keys * L
    unembed = 2 * cfg["d_model"] * cfg["vocab_size"] if sampled else 0
    return linear + attn + unembed
