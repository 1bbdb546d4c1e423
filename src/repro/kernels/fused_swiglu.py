"""Pallas TPU kernel: fused SwiGLU FFN — silu(x@Wg) * (x@Wu) @ Wd in one
HBM pass over the weights.

Every dense arch's FLOPs are d_ff-dominated; the unfused form writes the
(M, F) gate/up activations to HBM twice (2*M*F*2 bytes each way).  Fusing
keeps the (blk_m, blk_f) hidden tile in VMEM and accumulates the down
projection into a (blk_m, D) f32 scratch across the F grid dimension.

Tiling:
  grid = (M/blk_m, F/blk_f), F innermost
  per step: x_tile (blk_m, D) @ wg/wu tiles (D, blk_f) -> hidden (blk_m, blk_f)
            hidden @ wd tile (blk_f, D) accumulated into (blk_m, D) scratch
  VMEM: blk_m*D*2 (x) + 2*D*blk_f*2 (wg,wu) + blk_f*D*2 (wd) + blk_m*D*4 (acc)
  defaults blk_m=256, blk_f=512, D<=8192 -> ~28 MB? no: weights tiles
  dominate; for D=4096, blk_f=256: 3*4096*256*2 = 6.3 MB + acc 4 MB. OK.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_F = 256


def _swiglu_kernel(
    x_ref,        # (blk_m, D)
    wg_ref,       # (D, blk_f)
    wu_ref,       # (D, blk_f)
    wd_ref,       # (blk_f, D)
    o_ref,        # (blk_m, D)
    acc_ref,      # (blk_m, D) f32
):
    f_i = pl.program_id(1)
    n_f = pl.num_programs(1)

    @pl.when(f_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    u = jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h = (g * jax.lax.logistic(g) * u).astype(x.dtype)     # silu(g) * u
    acc_ref[...] += jax.lax.dot_general(
        h, wd_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(f_i == n_f - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f", "interpret"))
def fused_swiglu(
    x,            # (M, D)
    w_gate,       # (D, F)
    w_up,         # (D, F)
    w_down,       # (F, D)
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_f: int = DEFAULT_BLOCK_F,
    interpret: bool | None = None,
):
    M, D = x.shape
    F = w_gate.shape[1]
    block_m = min(block_m, M)
    block_f = min(block_f, F)
    assert M % block_m == 0, (M, block_m)
    assert F % block_f == 0, (F, block_f)

    grid = (M // block_m, F // block_f)

    return pl.pallas_call(
        _swiglu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((D, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((D, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((block_f, D), lambda mi, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_gate, w_up, w_down)
