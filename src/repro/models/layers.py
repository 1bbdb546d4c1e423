"""Shared model-zoo primitives (pure JAX, functional).

All functions take explicit param pytrees.  Sharding hints go through
``repro.distributed.sharding.constrain`` which is a no-op unless a mesh +
logical-axis rules context is active, so model code stays mesh-agnostic.
"""
from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:  # (d, h, hd) fused head projection
        fan_in = shape[0]
    s = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal offset, sliding window, padded-cache masking)
# ---------------------------------------------------------------------------


INVALID_POS = -(1 << 30)  # sentinel for unwritten ring-buffer slots

# 'auto' (default, §Perf-tuned): TRAIN uses the flash blocked-softmax path
# (bounded backward footprint for every arch incl. unsharded-head ones);
# PREFILL uses the exact chunked path (no backward -> footprint bounded by
# one chunk row, and ~17% less HLO-level HBM traffic than flash's carry
# rescaling).  'flash' / 'naive' force one implementation (tests, A/B).
ATTN_IMPL = "auto"

_attn_phase = threading.local()


@contextmanager
def attention_phase(phase: str):
    """'train' (default) or 'prefill' — set by Model entry points."""
    prev = getattr(_attn_phase, "v", "train")
    _attn_phase.v = phase
    try:
        yield
    finally:
        _attn_phase.v = prev

# block sizes tuned in the §Perf loop: boundary/carry traffic of the block
# loop scales ~1/block_k; (1024, 4096) cut the memory term 20% on
# mixtral train_4k vs (512, 1024) with no compute/collective change
FLASH_BLOCK_Q = 1024
FLASH_BLOCK_K = 4096


def attention(q, k, v, **kw):
    # decode (Sq == 1) has no S^2 blow-up, and the flash block-reshape of a
    # sequence-sharded KV cache forces an SPMD full-remat — keep decode on
    # the exact path (GSPMD turns its softmax reductions into the small
    # flash-decode style partial-max/sum all-reduces).
    if q.shape[1] == 1 or ATTN_IMPL == "naive":
        return attention_naive(q, k, v, **kw)
    if ATTN_IMPL == "flash":
        return flash_attention(q, k, v, **kw)
    # auto: exact-chunked for prefill, flash for train
    if getattr(_attn_phase, "v", "train") == "prefill":
        return attention_naive(q, k, v, **kw)
    return flash_attention(q, k, v, **kw)


def attention_naive(
    q,                      # (B, Sq, Hq, hd)
    k,                      # (B, Skv, Hkv, hd)
    v,                      # (B, Skv, Hkv, hd)
    *,
    q_offset=0,             # scalar or (B,): absolute position of q[:, 0]
    kv_lens=None,           # (B,) valid kv length (padded caches); None = all valid
    causal: bool = True,
    sliding_window: int = 0,
    kv_positions=None,      # (B, Skv) absolute key positions (ring buffers)
):
    """Reference GQA attention with flexible masking.

    Positions: query i has absolute position q_offset + i; key j has absolute
    position j unless ``kv_positions`` is given (SWA ring buffers, where slots
    hold non-contiguous positions and INVALID_POS marks unwritten slots).
    Causal mask admits key_pos <= query_pos; sliding window additionally
    requires key_pos > query_pos - window.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    g = Hq // Hkv

    # MXU semantics: bf16 operands, f32 accumulation via preferred_element_type.
    # (Never .astype(f32) the K/V cache — XLA hoists the convert above the
    # layer scan and materializes an f32 copy of the whole cache in HBM.)
    qf = (q * (1.0 / math.sqrt(hd))).astype(q.dtype).reshape(B, Sq, Hkv, g, hd)

    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(Sq)[None, :] + (q_off[:, None] if q_off.ndim else q_off)
    q_pos = jnp.broadcast_to(q_pos, (B, Sq))
    if kv_positions is None:
        k_pos = jnp.broadcast_to(jnp.arange(Skv)[None, :], (B, Skv))
    else:
        k_pos = kv_positions

    def block(q_blk, q_pos_blk):
        # q_blk: (B, Qc, Hkv, g, hd); exact softmax over full Skv
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q_blk, k, preferred_element_type=jnp.float32
        )
        mask = jnp.ones((B, q_blk.shape[1], Skv), dtype=bool)
        if causal:
            mask &= k_pos[:, None, :] <= q_pos_blk[:, :, None]
        if sliding_window:
            mask &= k_pos[:, None, :] > (q_pos_blk[:, :, None] - sliding_window)
        if kv_lens is not None:
            mask &= jnp.arange(Skv)[None, None, :] < kv_lens[:, None, None]
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum(
            "bhgqk,bkhd->bqhgd", probs, v, preferred_element_type=jnp.float32
        )

    Qc = _pick_chunk(Sq)
    if Qc == Sq:
        out = block(qf, q_pos)
    else:
        nQ = Sq // Qc
        q_c = qf.reshape(B, nQ, Qc, Hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)
        p_c = q_pos.reshape(B, nQ, Qc).transpose(1, 0, 2)
        out = jax.lax.map(lambda ab: block(*ab), (q_c, p_c))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, g, hd)
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def _pick_chunk(s: int, target: int = 512) -> int:
    """Largest divisor of s that is <= target (bounds attention score temps)."""
    if s <= target:
        return s
    for c in range(target, 0, -1):
        if s % c == 0:
            return c
    return s


def flash_attention(
    q,                      # (B, Sq, Hq, hd)
    k,                      # (B, Skv, Hkv, hd)
    v,                      # (B, Skv, Hkv, hd)
    *,
    q_offset=0,
    kv_lens=None,
    causal: bool = True,
    sliding_window: int = 0,
    kv_positions=None,
    block_q: int = 0,
    block_k: int = 0,
):
    """Blocked online-softmax attention — same semantics as
    :func:`attention_naive`, but never materializes the (Sq, Skv) score
    matrix: an outer ``lax.map`` over Q chunks and an inner ``lax.scan`` over
    KV blocks carry running (m, l, acc) in f32.  This is the jnp analogue of
    the Pallas kernels (kernels/chunked_prefill_attention.py) and gives XLA a
    program whose HBM traffic is O(S) per row instead of O(S^2)."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    g = Hq // Hkv

    qf = (q * (1.0 / math.sqrt(hd))).astype(q.dtype).reshape(B, Sq, Hkv, g, hd)

    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(Sq)[None, :] + (q_off[:, None] if q_off.ndim else q_off)
    q_pos = jnp.broadcast_to(q_pos, (B, Sq))
    if kv_positions is None:
        k_pos_all = jnp.broadcast_to(jnp.arange(Skv)[None, :], (B, Skv))
    else:
        k_pos_all = kv_positions

    blk_q = _pick_chunk(Sq, block_q or FLASH_BLOCK_Q)
    blk_k = _pick_chunk(Skv, block_k or FLASH_BLOCK_K)
    nQ, nK = Sq // blk_q, Skv // blk_k

    # (nK, B, blk_k, ...) KV blocks as scan xs
    k_b = k.reshape(B, nK, blk_k, Hkv, hd).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(B, nK, blk_k, Hkv, hd).transpose(1, 0, 2, 3, 4)
    kp_b = k_pos_all.reshape(B, nK, blk_k).transpose(1, 0, 2)

    kv_len_col = None if kv_lens is None else kv_lens[:, None, None]

    def q_chunk(args):
        q_blk, qp_blk = args                     # (B, blk_q, Hkv, g, hd), (B, blk_q)

        def kv_step(carry, xs):
            m, l, acc = carry
            k_blk, v_blk, kp_blk = xs            # (B, blk_k, Hkv, hd), (B, blk_k)
            s = jnp.einsum(
                "bqhgd,bkhd->bqhgk", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            )                                     # (B, blk_q, Hkv, g, blk_k)
            mask = jnp.ones((B, blk_q, blk_k), bool)
            if causal:
                mask &= kp_blk[:, None, :] <= qp_blk[:, :, None]
            if sliding_window:
                mask &= kp_blk[:, None, :] > (qp_blk[:, :, None] - sliding_window)
            if kv_len_col is not None:
                mask &= kp_blk[:, None, :] < kv_len_col
            maskh = mask[:, :, None, None, :]
            s = jnp.where(maskh, s, -jnp.inf)

            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # masked-out whole rows keep m == -inf; guard the exp
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            alpha = jnp.where(
                jnp.isfinite(m), jnp.exp(m - m_safe), 0.0
            )
            p = jnp.where(maskh, jnp.exp(s - m_safe[..., None]), 0.0)
            # row-sums consume the f32 p inside its producing fusion; only
            # the bf16 copy crosses the HBM boundary into the PV matmul
            # (halves the S^2 traffic vs an f32 p boundary)
            l = l * alpha + jnp.sum(p, axis=-1)
            p16 = p.astype(v_blk.dtype)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p16, v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, blk_q, Hkv, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, blk_q, Hkv, g), jnp.float32)
        a0 = jnp.zeros((B, blk_q, Hkv, g, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (k_b, v_b, kp_b))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return acc / l_safe[..., None]

    if nQ == 1:
        out = q_chunk((qf, q_pos))
    else:
        q_c = qf.reshape(B, nQ, blk_q, Hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)
        p_c = q_pos.reshape(B, nQ, blk_q).transpose(1, 0, 2)
        out = jax.lax.map(q_chunk, (q_c, p_c))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, g, hd)

    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block params + apply
# ---------------------------------------------------------------------------


def init_attention(rng, cfg, d_model: Optional[int] = None, cross: bool = False):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, hd), dt),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, hd), dt),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, hd), dt),
        "wo": dense_init(ks[3], (cfg.n_heads, hd, d), dt, scale=1.0 / math.sqrt(d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
    return p


def qkv_project(p, x, cfg, positions=None, rope: bool = True):
    """x: (B, S, D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with optional RoPE."""
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bq" in p:
            q = q + p["bq"]
            k = k + p["bk"]
            v = v + p["bv"]
        q = constrain(q, ("batch", "seq", "heads", None))
        k = constrain(k, ("batch", "seq", "kv_heads", None))
        v = constrain(v, ("batch", "seq", "kv_heads", None))
        if rope and positions is not None:
            # re-pin after rope: the roped outputs are new values, and an
            # unpinned k lets GSPMD pull the prefill-cache layout into the
            # attention loop (per-block all-gathers)
            q = constrain(apply_rope(q, positions, cfg.rope_theta),
                          ("batch", "seq", "heads", None))
            k = constrain(apply_rope(k, positions, cfg.rope_theta),
                          ("batch", "seq", "kv_heads", None))
        return q, k, v


def attn_output(p, attn, cfg):
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
        # row-parallel output: under sequence parallelism (act_seq -> model)
        # the partial sums reduce-scatter over S instead of all-reducing
        return constrain(out, ("batch", "act_seq", "embed"))


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------


def init_ffn(rng, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(rng, 3)
    dt = jnp.dtype(dtype)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dt),
        "w_up": dense_init(ks[1], (d_model, d_ff), dt),
        "w_down": dense_init(ks[2], (d_ff, d_model), dt),
    }


def ffn(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = constrain(h, ("batch", "seq", "mlp"))
    return constrain(h @ p["w_down"], ("batch", "act_seq", "embed"))


# ---------------------------------------------------------------------------
# Mixture of Experts (grouped GShard-style dispatch; capacity-bounded)
# ---------------------------------------------------------------------------

MOE_GROUP_SIZE = 4096  # tokens per capacity group (hillclimb knob)


def init_moe(rng, cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    return {
        "router": dense_init(ks[0], (d, m.n_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_ff), dt),
        "w_up": dense_init(ks[2], (m.n_experts, d, m.d_ff), dt),
        "w_down": dense_init(ks[3], (m.n_experts, m.d_ff, d), dt),
    }


def moe_capacity(tokens_per_group: int, n_experts: int, top_k: int, cf: float) -> int:
    return max(1, math.ceil(tokens_per_group * top_k / n_experts * cf))


def moe_ffn(p, x, cfg, group_size: int = 0):
    """x: (B, S, D) -> (B, S, D).  Router in f32; experts in compute dtype.

    Tokens are reshaped into capacity groups of ``group_size`` tokens; each
    expert serves ``C = ceil(group_tokens * top_k / E * capacity_factor)``
    slots per group (GShard).  Overflowing tokens are dropped (residual path
    keeps them intact), the standard capacity-factor semantics.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    gsz = group_size or min(MOE_GROUP_SIZE, T)
    if T % gsz:
        gsz = math.gcd(T, gsz) if math.gcd(T, gsz) > 1 else T
    G = T // gsz
    xg = x.reshape(G, gsz, D)
    xg = constrain(xg, ("batch", None, "embed"))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                       # (G, t, E)
    top_w, top_idx = jax.lax.top_k(probs, m.top_k)                # (G, t, K)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)  # renormalize

    C = moe_capacity(gsz, m.n_experts, m.top_k, m.capacity_factor)

    dispatch = jnp.zeros((G, gsz, m.n_experts, C), dtype=x.dtype)
    combine = jnp.zeros((G, gsz, m.n_experts, C), dtype=jnp.float32)
    counts = jnp.zeros((G, m.n_experts), dtype=jnp.int32)
    for j in range(m.top_k):
        mask_j = jax.nn.one_hot(top_idx[:, :, j], m.n_experts, dtype=jnp.int32)  # (G,t,E)
        pos_j = counts[:, None, :] + jnp.cumsum(mask_j, axis=1) - mask_j         # (G,t,E)
        within = (pos_j < C) & (mask_j > 0)
        slot = jnp.sum(pos_j * mask_j, axis=-1)                                  # (G,t)
        slot_oh = jax.nn.one_hot(slot, C, dtype=x.dtype)                         # (G,t,C)
        d_j = within.astype(x.dtype)[..., None] * slot_oh[:, :, None, :]         # (G,t,E,C)
        dispatch = dispatch + d_j
        combine = combine + top_w[:, :, j, None, None].astype(jnp.float32) * d_j.astype(jnp.float32)
        counts = counts + jnp.sum(mask_j * within.astype(jnp.int32), axis=1)

    # (E, G, C, D): every expert serves G*C slots
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch, xg)
    expert_in = constrain(expert_in, ("experts", None, None, "embed"))
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("egcd,edf->egcf", expert_in, p["w_up"])
    h = constrain(h, ("experts", None, None, "moe_mlp"))
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["w_down"])
    expert_out = constrain(expert_out, ("experts", None, None, "embed"))

    out = jnp.einsum("gtec,egcd->gtd", combine.astype(expert_out.dtype), expert_out)
    return out.reshape(B, S, D)


def moe_ffn_scatter(p, x, cfg, group_size: int = 0):
    """Beyond-paper optimized MoE path: group-local sort/gather dispatch.

    vs the one-hot GShard einsums: no (G, t, E, C) dispatch/combine tensors
    (O(T*E*C) memory + FLOPs) -- tokens scatter directly into per-expert
    buffers.  Groups ride the batch sharding, so dispatch is LOCAL to each
    data shard (zero dispatch collectives under pjit); only the usual TP
    contribution of the expert matmuls communicates."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    gsz = group_size or min(MOE_GROUP_SIZE, T)
    if T % gsz:
        gsz = math.gcd(T, gsz) if math.gcd(T, gsz) > 1 else T
    G = T // gsz
    xg = x.reshape(G, gsz, D)
    xg = constrain(xg, ("batch", None, "embed"))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, m.top_k)                 # (G, t, K)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)

    C = moe_capacity(gsz, m.n_experts, m.top_k, m.capacity_factor)
    flat_e = top_idx.reshape(G, gsz * m.top_k)                     # (G, tK)
    eq = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)      # (G, tK, E)
    pos = jnp.cumsum(eq, axis=1) - eq
    slot_in_e = jnp.sum(pos * eq, axis=-1)                         # (G, tK)
    ok = slot_in_e < C
    dest = jnp.where(ok, flat_e * C + slot_in_e, m.n_experts * C)  # (G, tK)

    src = jnp.repeat(xg, m.top_k, axis=1)                          # (G, tK, D)

    def scatter_one(dest_g, src_g):
        buf = jnp.zeros((m.n_experts * C + 1, D), dtype=x.dtype)
        return buf.at[dest_g].set(src_g, mode="drop")

    buf = jax.vmap(scatter_one)(dest, src)                         # (G, EC+1, D)
    expert_in = buf[:, :-1].reshape(G, m.n_experts, C, D)
    expert_in = constrain(expert_in, ("batch", "experts", None, "embed"))

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    h = constrain(h, ("batch", "experts", None, "moe_mlp"))
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])      # (G, E, C, D)
    expert_out = constrain(expert_out, ("batch", "experts", None, "embed"))

    flat_out = expert_out.reshape(G, m.n_experts * C, D)
    safe = jnp.clip(dest, 0, m.n_experts * C - 1)
    gathered = jnp.take_along_axis(flat_out, safe[..., None], axis=1)
    gathered = jnp.where(ok[..., None], gathered, 0.0)             # (G, tK, D)
    w = top_w.reshape(G, gsz * m.top_k, 1).astype(gathered.dtype)
    out = jnp.sum((gathered * w).reshape(G, gsz, m.top_k, D), axis=2)
    return out.reshape(B, S, D)
