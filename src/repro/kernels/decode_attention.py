"""Pallas TPU kernel: flash-decode attention (single-token GQA decode).

decode_32k / long_500k shapes are HBM-bound KV streaming: one new query
token per sequence attends to a long cache.  The kernel streams K/V blocks
once and keeps the online-softmax state (m, l, acc) in f32 VMEM scratch.

TPU adaptation:
  * all `group` query heads of one KV head are processed together as the
    (group, hd) left operand — an MXU-friendly tall-skinny matmul against
    each (blk_k, hd) KV tile (the GPU analogue uses warp-level broadcast;
    on TPU the group dimension rides the sublane axis).
  * kv_lens via scalar prefetch: tiles past the valid length are skipped
    entirely, so decoding a 1k-token sequence in a 32k cache touches only
    1k tokens of HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_K = 256

NEG_INF = -1e30


def _decode_kernel(
    kv_len_ref,     # (B,) scalar prefetch
    q_ref,          # (group, hd)
    k_ref,          # (blk_k, hd)
    v_ref,          # (blk_k, hd)
    o_ref,          # (group, hd)
    m_ref,          # (group,) f32
    l_ref,          # (group,) f32
    acc_ref,        # (group, hd) f32
    *,
    block_k: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    kv_i = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[b]
    k_pos = kv_i * block_k + jax.lax.iota(jnp.int32, block_k)

    @pl.when(k_pos[0] < kv_len)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * sm_scale        # (g, hd)
        k = k_ref[...].astype(jnp.float32)                   # (blk_k, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (g, blk_k)
        mask = k_pos[None, :] < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)

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


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q,            # (B, Hq, hd) one token per sequence
    k_cache,      # (B, S, Hkv, hd)
    v_cache,      # (B, S, Hkv, hd)
    kv_lens,      # (B,) int32
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    B, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv

    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)

    grid = (B, Hkv, S // block_k)

    kernel = functools.partial(
        _decode_kernel, block_k=block_k, sm_scale=1.0 / math.sqrt(hd)
    )

    q_g = q.reshape(B, Hkv, group, hd)
    k_t = k_cache.transpose(0, 2, 1, 3)    # (B, Hkv, S, hd)
    v_t = v_cache.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, None, group, hd), lambda b, h, ki, *_: (b, h, 0, 0)
                ),
                pl.BlockSpec(
                    (None, None, block_k, hd), lambda b, h, ki, *_: (b, h, ki, 0)
                ),
                pl.BlockSpec(
                    (None, None, block_k, hd), lambda b, h, ki, *_: (b, h, ki, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, None, group, hd), lambda b, h, ki, *_: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(kv_lens.astype(jnp.int32), q_g, k_t, v_t)

    return out.reshape(B, Hq, hd)
