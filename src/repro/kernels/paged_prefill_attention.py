"""Pallas TPU kernel: paged chunked-prefill attention.

The chunked-prefill engine's hot op against a *paged* KV cache: a chunk of Q
tokens (one scheduling round) attends to its sequence's prefix KV plus its
own keys with a causal offset, where K/V live in a shared physical page pool
``(n_pages, page_size, Hkv, hd)`` — or its row-major flattening
``(n_pages, page_size, Hkv*hd)``, which the engine stores — addressed
through a per-sequence block table (same layout as
``paged_decode_attention``).

Grid: ``(B, Hq, Sq // block_q, n_tiles)`` — the innermost dimension walks the
sequence's block table one *tile* of ``pages_per_tile`` pages at a time.  The
prefetched table steers per-page async copies (K/V live in compiler-placed
memory, ``pl.ANY``) that gather the scattered physical pages into one
contiguous ``(pages_per_tile * page_size, width)`` VMEM tile, so the MXU sees
wide K/V operands even at small page sizes; the online-softmax (m, l, acc)
scratch carries across tiles exactly as the dense kernel carries across KV
blocks.  Tiles entirely above the causal diagonal or past ``kv_len`` are
skipped before any DMA is issued, so work stays ~O(prefix + chunk^2/2) per
sequence regardless of pool size.

Each copy moves the 128-lane-aligned block of whole heads that holds the
program's kv head, and the query head is zero-padded into its own lanes of
that block — see ``paged_decode_attention``'s module docstring.
``buffering_depth`` and the fused head-interleaved layout work exactly as
there: tile ``t`` computes out of buffer slot ``t % depth`` while tile
``t+depth-1``'s gather is already in flight, and the fused pool needs only
ONE async copy per page to feed both K and V.  Live tiles form a contiguous
prefix (the causal bound ``tile_start <= last query position`` and the
length bound ``tile_start < kv_len`` are both monotone in the tile index),
so every issued copy is waited within the same inner tile loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.paged_decode_attention import (
    _kernel_scratch,
    _pad_tables,
    _tile_copies,
    flat_pools,
    place_heads,
    take_heads,
)

DEFAULT_BLOCK_Q = 128

NEG_INF = -1e30


def _paged_prefill_kernel(
    # prefetched scalars
    block_tables_ref,   # (B, n_tiles * pages_per_tile)
    kv_len_ref,         # (B,) valid kv length (prefix + chunk)
    q_offset_ref,       # (B,) absolute position of q[:, 0]
    # blocked operands
    q_ref,              # (blk_q, width) lane-placed queries of one head
    *refs,              # pools (1 fused | 2 split), o_ref, m, l, acc, tiles, sem
    block_q: int,
    page_size: int,
    pages_per_tile: int,
    heads_per_block: int,
    sm_scale: float,
    depth: int,
    n_tiles: int,
    n_pools: int,
    width: int,
):
    pools = refs[:n_pools]
    o_ref, m_ref, l_ref, acc_ref = refs[n_pools:n_pools + 4]
    tiles, sem = refs[n_pools + 4:-1], refs[-1]

    b = pl.program_id(0)
    h = pl.program_id(1)
    tile_i = pl.program_id(3)
    tile = page_size * pages_per_tile

    kv_len = kv_len_ref[b]
    q_off = q_offset_ref[b]

    q_i = pl.program_id(2)
    q_start = q_off + q_i * block_q
    q_pos = q_start + jax.lax.iota(jnp.int32, block_q)

    def live(t):
        # whole-tile skip: above the causal diagonal or past the valid
        # length — dead tiles issue no DMA.  The last query position is a
        # scalar: Mosaic cannot lower an element read of a vector.
        return (t * tile <= q_start + block_q - 1) & (t * tile < kv_len)

    blk = h // heads_per_block

    def copies(t, slot):
        return _tile_copies(
            block_tables_ref, blk, t, slot, pools, tiles, sem,
            page_size=page_size, pages_per_tile=pages_per_tile, width=width,
            b=b,
        )

    @pl.when(tile_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # prologue: put tiles 0..depth-2 in flight before the first wait
        for d in range(min(depth - 1, n_tiles)):
            @pl.when(live(d))
            def _issue_ahead(d=d):
                for c in copies(d, d % depth):
                    c.start()

    # steady state: issue tile t+depth-1 before waiting on tile t (depth=1:
    # issue tile t itself — the synchronous path)
    nxt = tile_i + (depth - 1)
    @pl.when((nxt < n_tiles) & live(nxt))
    def _issue():
        for c in copies(nxt, nxt % depth):
            c.start()

    slot = tile_i % depth

    @pl.when(live(tile_i))
    def _compute():
        for c in copies(tile_i, slot):
            c.wait()
        k = tiles[0][slot]                                # (tile, width)
        v = tiles[-1][slot]                               # fused: same tile

        tile_start = tile_i * tile
        k_pos = tile_start + jax.lax.iota(jnp.int32, tile)
        q = q_ref[...].astype(jnp.float32) * sm_scale
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (blk_q, tile)
        mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(tile_i == n_tiles - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def _paged_prefill_call(q, pools, block_tables, kv_lens, q_offset, *,
                        block_q, pages_per_tile, buffering_depth, interpret,
                        fused):
    B, Sq, Hq, hd = q.shape
    page_size = pools[0].shape[1]
    pools, Hkv, r, width = flat_pools(pools, hd, fused)
    assert Hq % Hkv == 0, (Hq, Hkv)
    assert buffering_depth >= 1, buffering_depth
    group = Hq // Hkv
    n_slots = width // hd

    block_q = min(block_q, Sq)
    assert Sq % block_q == 0, (Sq, block_q)

    block_tables, n_tiles = _pad_tables(
        block_tables.astype(jnp.int32), pages_per_tile
    )

    grid = (B, Hq, Sq // block_q, n_tiles)
    kernel = functools.partial(
        _paged_prefill_kernel, block_q=block_q, page_size=page_size,
        pages_per_tile=pages_per_tile, heads_per_block=r * group,
        sm_scale=1.0 / math.sqrt(hd), depth=buffering_depth, n_tiles=n_tiles,
        n_pools=len(pools), width=width,
    )

    # q head h reads kv head h // group, slot j = (h // group) % r of its
    # lane block: K lanes in slot j (split) or 2j (fused), output the V slot
    j_of_head = (jnp.arange(Hq) // group) % r
    k_slot = (j_of_head * (2 if fused else 1))[:, None]
    v_slot = k_slot + (1 if fused else 0)
    q_t = place_heads(q.transpose(0, 2, 1, 3), k_slot, n_slots)  # (B,Hq,Sq,W)

    tile = page_size * pages_per_tile
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, None, block_q, width),
                    lambda b, h, qi, ti, *_: (b, h, qi, 0),
                ),
                # K/V stay unblocked: the kernel gathers pages itself via
                # per-page async copies steered by the prefetched table
                *([pl.BlockSpec(memory_space=pl.ANY)] * len(pools)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, block_q, width),
                lambda b, h, qi, ti, *_: (b, h, qi, 0),
            ),
            scratch_shapes=_kernel_scratch(
                buffering_depth, tile, width, block_q, pools[0].dtype,
                len(pools), pages_per_tile,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, width), q.dtype),
        interpret=resolve_interpret(interpret),
    )(
        block_tables, kv_lens.astype(jnp.int32), q_offset.astype(jnp.int32),
        q_t, *pools,
    )

    return take_heads(out, v_slot, n_slots).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "pages_per_tile", "buffering_depth", "interpret"),
)
def paged_prefill_attention(
    q,              # (B, Sq, Hq, hd) the prefill chunk's queries
    k_pages,        # (n_pages, page_size, Hkv, hd) or (.., Hkv*hd) pool
    v_pages,        # same shape as k_pages
    block_tables,   # (B, max_pages) int32 physical page ids
    kv_lens,        # (B,) int32 valid KV length (prefix + chunk)
    q_offset,       # (B,) int32 absolute position of q[:, 0]
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    pages_per_tile: int = 1,
    buffering_depth: int = 1,
    interpret: bool | None = None,
):
    return _paged_prefill_call(
        q, (k_pages, v_pages), block_tables, kv_lens, q_offset,
        block_q=block_q, pages_per_tile=pages_per_tile,
        buffering_depth=buffering_depth, interpret=interpret, fused=False,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "pages_per_tile", "buffering_depth", "interpret"),
)
def paged_prefill_attention_fused(
    q,              # (B, Sq, Hq, hd)
    kv_pages,       # (n_pages, page_size, 2*Hkv, hd) or (.., 2*Hkv*hd)
    block_tables,   # (B, max_pages) int32
    kv_lens,        # (B,) int32
    q_offset,       # (B,) int32
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    pages_per_tile: int = 1,
    buffering_depth: int = 1,
    interpret: bool | None = None,
):
    return _paged_prefill_call(
        q, (kv_pages,), block_tables, kv_lens, q_offset,
        block_q=block_q, pages_per_tile=pages_per_tile,
        buffering_depth=buffering_depth, interpret=interpret, fused=True,
    )
