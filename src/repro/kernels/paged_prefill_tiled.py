"""Served paged chunked-prefill attention in plain ``jax.numpy``: the path
the engine takes with ``use_pallas=False``.

It computes what ``ref.paged_prefill_attention_ref`` computes -- a chunk of
queries against its sequence's prefix and itself, through the block table,
with the same causal offset, masks and scale -- but walks the block table
one tile of ``TILE_KEYS`` keys at a time with an online softmax, so that
its temporaries are rows x heads x tile and not rows x heads x the whole
table.  Each tile gathers its own pages; ``QK^T`` takes the pool's operands
with float32 accumulation (the products of casting both to float32), and the
probabilities and ``PV`` are float32.  The running max, sum and output stay
float32 per (row, head).

Tiles past the round's longest ``kv_len`` hold only masked keys, whose
weights are exactly zero, so the walk stops there: the work follows the
longest real context, not ``max_context``.  A row with no valid key comes
out NaN, as the oracle's all-masked softmax does.
"""
from __future__ import annotations

import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

# keys a tile walks (whole pages): the float32 scores of a C=256 round of
# 8 rows of 96 heads are then 0.4 GB where the whole 4,624-key table's were
# 3.4 GB; 256 saved 0.08 GB of that step's 2.07 GB in the compile rehearsal
TILE_KEYS = 512


def _tiled(q, gather: Callable, n_kv_heads: int, block_tables, page_size: int,
           kv_lens, q_offset):
    """``gather(ids (B, n))`` -> K, V of those pages, each
    ``(B, n * page_size, n_kv_heads, hd)``."""
    B, Sq, Hq, hd = q.shape
    tp = max(1, TILE_KEYS // page_size)
    T = tp * page_size
    n_tiles = -(-block_tables.shape[1] // tp)
    # pad entries repeat a valid page; their positions lie past every kv_len
    tables = jnp.pad(block_tables, ((0, 0), (0, n_tiles * tp - block_tables.shape[1])),
                     mode="edge")
    Hkv = n_kv_heads
    g = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    # (B, Hkv, g*Sq, hd): each kv head's query rows, grouped head-major
    qt = q.reshape(B, Sq, Hkv, g, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, g * Sq, hd)
    q_pos = q_offset[:, None] + jnp.arange(Sq)[None, :]              # (B, Sq)

    def body(t, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, t * tp, tp, axis=1)
        k, v = gather(ids)
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)      # (B, Hkv, T, hd)
        s = jnp.einsum("bhrd,bhkd->bhrk", qt, k,
                       preferred_element_type=jnp.float32) * scale
        k_pos = t * T + jnp.arange(T)
        mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & (
            k_pos[None, None, :] < kv_lens[:, None, None])           # (B, Sq, T)
        s = jnp.where(mask[:, None, None], s.reshape(B, Hkv, g, Sq, T),
                      -jnp.inf).reshape(B, Hkv, g * Sq, T)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with no valid key yet keep a zero shift: their weights are 0
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m - shift)
        p = jnp.exp(s - shift[..., None])
        l = alpha * l + p.sum(axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhrk,bhkd->bhrd", p, v.astype(jnp.float32))
        return m_new, l, acc

    rows = (B, Hkv, g * Sq)
    init = (jnp.full(rows, -jnp.inf, jnp.float32), jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows + (hd,), jnp.float32))
    live = jnp.clip(-(-jnp.max(kv_lens) // T), 0, n_tiles)
    _, l, acc = jax.lax.fori_loop(0, live, body, init)
    out = (acc / l[..., None]).reshape(B, Hkv, g, Sq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd).astype(q.dtype)


def _rows(pages, ids, head_dim: int, heads: int) -> jax.Array:
    """Pages ``ids (B, n)`` of a pool stored ``(n_pages, ps, H*hd)`` or
    ``(n_pages, ps, H, hd)``, as ``(B, n*ps, heads, hd)``."""
    B, n = ids.shape
    return pages[ids].reshape(B, n * pages.shape[1], heads, head_dim)


def paged_prefill_attention_tiled(q, k_pages, v_pages, block_tables, kv_lens,
                                  q_offset):
    """(B, Sq, Hq, hd) chunk against split K and V pools
    ``(n_pages, ps, Hkv*hd)`` (or ``(n_pages, ps, Hkv, hd)``) through
    per-sequence block tables ``(B, max_pages)``; query ``i`` of row ``b``
    sits at ``q_offset[b] + i`` and sees keys up to it and below
    ``kv_lens[b]``."""
    hd = q.shape[-1]
    heads = math.prod(k_pages.shape[2:]) // hd

    def gather(ids) -> Tuple[jax.Array, jax.Array]:
        return (_rows(k_pages, ids, hd, heads), _rows(v_pages, ids, hd, heads))

    return _tiled(q, gather, heads, block_tables, k_pages.shape[1], kv_lens,
                  q_offset)


def paged_prefill_attention_tiled_fused(q, kv_pages, block_tables, kv_lens,
                                        q_offset):
    """``paged_prefill_attention_tiled`` over a fused head-interleaved pool
    ``(n_pages, ps, 2*Hkv*hd)``, heads ``[K0, V0, K1, V1, ...]``."""
    hd = q.shape[-1]
    heads = math.prod(kv_pages.shape[2:]) // hd

    def gather(ids) -> Tuple[jax.Array, jax.Array]:
        kv = _rows(kv_pages, ids, hd, heads)
        return kv[:, :, 0::2], kv[:, :, 1::2]

    return _tiled(q, gather, heads // 2, block_tables, kv_pages.shape[1],
                  kv_lens, q_offset)
