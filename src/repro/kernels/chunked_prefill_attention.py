"""Pallas TPU kernel: chunked-prefill attention (the hot op of the paper's
serving engine).

A chunk of Q tokens (one scheduling round's prefill chunk) attends to the
prefix KV cache plus its own keys with a causal offset — exactly the
computation a chunked-prefill engine issues per round (Sarathi-style).

TPU adaptation (vs the GPU flash kernels the paper's engines use):
  * Q tile x KV tile 128 — MXU-aligned (128x128 systolic array).
  * Online softmax: running (m, l, acc) carried in f32 VMEM scratch across
    the KV grid dimension (innermost), one HBM pass over K/V.
  * GQA: grid iterates query heads; the KV block index maps h -> h // group
    so each KV head's cache tile is streamed once per query-head group.
  * Per-batch q_offset and kv_len arrive via scalar prefetch (SMEM): tiles
    entirely above the causal diagonal or past kv_len skip their matmuls
    (`tile_live`), keeping work ~O(prefix + chunk^2/2), not O(Skv * chunk).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

NEG_INF = -1e30


def _attn_kernel(
    # prefetched scalars
    q_offset_ref,   # (B,) absolute position of q[:, 0]
    kv_len_ref,     # (B,) valid kv length
    # blocked operands
    q_ref,          # (blk_q, hd)
    k_ref,          # (blk_k, hd)
    v_ref,          # (blk_k, hd)
    # blocked output
    o_ref,          # (blk_q, hd)
    # scratch
    m_ref,          # (blk_q,) f32 running max
    l_ref,          # (blk_q,) f32 running sum
    acc_ref,        # (blk_q, hd) f32 accumulator
    *,
    block_q: int,
    block_k: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    kv_i = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = q_offset_ref[b]
    kv_len = kv_len_ref[b]

    q_i = pl.program_id(2)
    q_pos = q_off + q_i * block_q + jax.lax.iota(jnp.int32, block_q)   # (blk_q,)
    k_pos = kv_i * block_k + jax.lax.iota(jnp.int32, block_k)          # (blk_k,)

    # whole-tile skip: first key pos vs the highest query pos in this tile
    tile_live = (k_pos[0] <= q_pos[-1]) & (k_pos[0] < kv_len)

    @pl.when(tile_live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * sm_scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (blk_q, blk_k)
        mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "interpret"),
)
def chunked_prefill_attention(
    q,            # (B, Sq, Hq, hd)
    k_cache,      # (B, Skv, Hkv, hd)
    v_cache,      # (B, Skv, Hkv, hd)
    kv_lens,      # (B,) int32
    q_offset,     # (B,) int32
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv

    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0, (Sq, block_q)
    assert Skv % block_k == 0, (Skv, block_k)

    grid = (B, Hq, Sq // block_q, Skv // block_k)

    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q,
        block_k=block_k,
        sm_scale=1.0 / math.sqrt(hd),
    )

    # layouts: head dim before seq for contiguous (seq, hd) tiles
    q_t = q.transpose(0, 2, 1, 3)          # (B, Hq, Sq, hd)
    k_t = k_cache.transpose(0, 2, 1, 3)    # (B, Hkv, Skv, hd)
    v_t = v_cache.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, None, block_q, hd),
                    lambda b, h, qi, ki, *_: (b, h, qi, 0),
                ),
                pl.BlockSpec(
                    (None, None, block_k, hd),
                    lambda b, h, qi, ki, *_, g=group: (b, h // g, ki, 0),
                ),
                pl.BlockSpec(
                    (None, None, block_k, hd),
                    lambda b, h, qi, ki, *_, g=group: (b, h // g, ki, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, None, block_q, hd),
                lambda b, h, qi, ki, *_: (b, h, qi, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q_offset.astype(jnp.int32), kv_lens.astype(jnp.int32), q_t, k_t, v_t)

    return out.transpose(0, 2, 1, 3)       # (B, Sq, Hq, hd)
