"""Jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects kernel vs pure-jnp path; on CPU the kernels run in
interpret mode (Python-executed kernel bodies — correctness, not speed); on
TPU the same calls compile to Mosaic; any other platform is refused
(``repro.kernels.interpret_mode``).  The engine flips this with one flag.
The pure-jnp path is the oracle (``ref``) everywhere but in paged prefill,
which serves ``paged_prefill_tiled``: the oracle's temporaries grow with the
whole block table.

Paged pools arrive as ``(n_pages, page_size, H, hd)`` or flattened
``(n_pages, page_size, H*hd)`` (the engine's stored layout); the oracles see
the former.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.chunked_prefill_attention import chunked_prefill_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.fused_swiglu import fused_swiglu
from repro.kernels.paged_decode_attention import (
    paged_decode_attention,
    paged_decode_attention_fused,
)
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention,
    paged_prefill_attention_fused,
)
from repro.kernels.paged_prefill_tiled import (
    paged_prefill_attention_tiled,
    paged_prefill_attention_tiled_fused,
)
from repro.kernels.swap import (
    swap_gather_pages, swap_gather_pages_q8, swap_scatter_pages,
    swap_scatter_pages_q8,
)


def _attention(fn):
    """Run ``fn`` under the ``attention`` name scope: every device op of the
    oracle or the kernel carries it in its metadata (the compiled program is
    unchanged)."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("attention"):
            return fn(*args, **kwargs)
    return scoped


def _heads(pages, head_dim):
    """A paged pool as ``(n_pages, page_size, H, hd)`` for the oracles."""
    return pages.reshape(pages.shape[:2] + (-1, head_dim))


@_attention
def prefill_chunk_attention(q, k_cache, v_cache, kv_lens, q_offset, *,
                            use_pallas: bool = True, block_q: int = 128,
                            block_k: int = 128):
    """(B, Sq, Hq, hd) chunk vs (B, Skv, Hkv, hd) cache with causal offset."""
    if not use_pallas:
        return ref.chunked_prefill_attention_ref(q, k_cache, v_cache, kv_lens, q_offset)
    return chunked_prefill_attention(
        q, k_cache, v_cache, kv_lens, q_offset,
        block_q=block_q, block_k=block_k,
    )


@_attention
def flash_decode_attention(q, k_cache, v_cache, kv_lens, *,
                           use_pallas: bool = True, block_k: int = 256):
    """(B, Hq, hd) single-token decode vs (B, S, Hkv, hd) cache."""
    if not use_pallas:
        return ref.decode_attention_ref(q, k_cache, v_cache, kv_lens)
    return decode_attention(
        q, k_cache, v_cache, kv_lens, block_k=block_k
    )


@_attention
def paged_prefill_chunk_attention(q, k_pages, v_pages, block_tables, kv_lens,
                                  q_offset, *, use_pallas: bool = True,
                                  block_q: int = 128, pages_per_tile: int = 1,
                                  buffering_depth: int = 1):
    """(B, Sq, Hq, hd) chunk vs a (n_pages, ps, Hkv, hd) physical page pool
    addressed through per-sequence block tables, with causal offset.
    ``pages_per_tile`` pages are DMA-gathered into one MXU K/V tile per grid
    step of the kernel; ``buffering_depth`` gathers run ahead of the dot (1 =
    synchronous).  Without Pallas, ``paged_prefill_attention_tiled``."""
    if not use_pallas:
        return paged_prefill_attention_tiled(
            q, k_pages, v_pages, block_tables, kv_lens, q_offset)
    return paged_prefill_attention(
        q, k_pages, v_pages, block_tables, kv_lens, q_offset,
        block_q=block_q, pages_per_tile=pages_per_tile,
        buffering_depth=buffering_depth,
    )


@_attention
def paged_prefill_chunk_attention_fused(q, kv_pages, block_tables, kv_lens,
                                        q_offset, *, use_pallas: bool = True,
                                        block_q: int = 128,
                                        pages_per_tile: int = 1,
                                        buffering_depth: int = 1):
    """``paged_prefill_chunk_attention`` over a fused head-interleaved pool
    ``(n_pages, ps, 2*Hkv, hd)`` — one DMA per page feeds both K and V."""
    if not use_pallas:
        return paged_prefill_attention_tiled_fused(
            q, kv_pages, block_tables, kv_lens, q_offset)
    return paged_prefill_attention_fused(
        q, kv_pages, block_tables, kv_lens, q_offset,
        block_q=block_q, pages_per_tile=pages_per_tile,
        buffering_depth=buffering_depth,
    )


@_attention
def paged_flash_decode_attention(q, k_pages, v_pages, block_tables, kv_lens, *,
                                 use_pallas: bool = True,
                                 pages_per_tile: int = 1,
                                 buffering_depth: int = 1):
    """(B, Hq, hd) single-token decode vs a paged pool + block tables."""
    if not use_pallas:
        hd = q.shape[-1]
        return ref.paged_decode_attention_ref(
            q, _heads(k_pages, hd), _heads(v_pages, hd), block_tables, kv_lens)
    return paged_decode_attention(
        q, k_pages, v_pages, block_tables, kv_lens,
        pages_per_tile=pages_per_tile, buffering_depth=buffering_depth,
    )


@_attention
def paged_flash_decode_attention_fused(q, kv_pages, block_tables, kv_lens, *,
                                       use_pallas: bool = True,
                                       pages_per_tile: int = 1,
                                       buffering_depth: int = 1):
    """``paged_flash_decode_attention`` over a fused head-interleaved pool."""
    if not use_pallas:
        return ref.paged_decode_attention_fused_ref(
            q, _heads(kv_pages, q.shape[-1]), block_tables, kv_lens)
    return paged_decode_attention_fused(
        q, kv_pages, block_tables, kv_lens,
        pages_per_tile=pages_per_tile, buffering_depth=buffering_depth,
    )


def swiglu_ffn(x, w_gate, w_up, w_down, *, use_pallas: bool = True,
               block_m: int = 256, block_f: int = 256):
    """(M, D) x (D, F) SwiGLU; fused single-HBM-pass kernel on TPU."""
    if not use_pallas:
        return ref.fused_swiglu_ref(x, w_gate, w_up, w_down)
    return fused_swiglu(
        x, w_gate, w_up, w_down,
        block_m=block_m, block_f=block_f,
    )


def gather_swap_pages(pages, ids, *, use_pallas: bool = True):
    """Collect scattered physical pages ``pages[:, ids]`` into one contiguous
    staging tensor (swap-out: the engine host-copies the result as a single
    dense DMA)."""
    return swap_gather_pages(
        pages, ids, use_pallas=use_pallas
    )


def scatter_swap_pages(pages, ids, staged, *, use_pallas: bool = True):
    """Write a staging tensor back into freshly allocated physical pages
    (swap-in restore; ``pages`` is donated and updated in place)."""
    return swap_scatter_pages(
        pages, ids, staged, use_pallas=use_pallas
    )


def gather_swap_pages_q8(pages, ids, *, head_dim: int,
                         use_pallas: bool = True):
    """Gather + INT8-quantize staging pages in one fused pass (host tier
    with ``host_kv_dtype="int8"``): returns ``(q, scales)``, scaled per
    ``head_dim``-lane head of the flat page row."""
    return swap_gather_pages_q8(
        pages, ids, head_dim=head_dim, use_pallas=use_pallas
    )


def scatter_swap_pages_q8(pages, ids, q_staged, scales, *,
                          use_pallas: bool = True):
    """Dequantize + scatter INT8 staging pages back into physical pages
    (``pages`` donated and updated in place)."""
    return swap_scatter_pages_q8(
        pages, ids, q_staged, scales, use_pallas=use_pallas
    )
