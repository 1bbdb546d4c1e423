"""Pallas TPU kernel: paged flash-decode attention (block-table K/V gather).

The physical KV cache is a pool of fixed-size pages shared by all sequences
(vLLM layout): ``k_pages/v_pages: (n_pages, page_size, Hkv, hd)``, or the
same pool flattened row-major to ``(n_pages, page_size, Hkv*hd)`` — the
layout the engine stores, because the kernels DMA pages from it as it
stands (for a ``(.., Hkv, 64)`` array a TPU picks a layout with the page
index minor, where no page is one contiguous block).
Each sequence owns a *block table* — the ordered list of physical page ids
backing its logical token positions — so capacity scales with tokens
actually resident, not ``n_slots x max_context``.

Indirection rides scalar prefetch: the block table and per-sequence kv
lengths land in SMEM before the kernel body runs.  Each grid step covers one
*tile* of ``pages_per_tile`` pages: the kernel issues one async copy per page
(K and V live in compiler-placed memory, ``pl.ANY``), gathering the
scattered physical pages into a contiguous
``(pages_per_tile * page_size, width)`` VMEM tile, then runs one MXU dot over
the whole tile.  At small page sizes this is the difference between feeding
the MXU 16-row slivers and feeding it full 128-row tiles — the per-tile
online-softmax (m, l, acc) scratch carries across tiles exactly as the dense
``decode_attention`` kernel carries across KV blocks.  Tiles entirely past
``kv_len`` are skipped before any DMA is issued.

Lane blocks: each copy moves ``width`` lanes of a page row — the narrowest
run of whole heads that is a multiple of 128 lanes (``lane_block_heads``),
since Mosaic refuses narrower lane slices.  At head_dim 64 a block holds two
heads, so each query row is zero-padded into its own head's lanes
(``place_heads``): the dot against the whole block scores only that head's
keys, and the output keeps only its head's lanes (``take_heads``).

Two orthogonal knobs hide the gather latency behind the MXU dot:

* ``buffering_depth`` — the VMEM tile scratch and DMA semaphores carry a
  leading ``depth`` axis; tile ``t`` lands in buffer slot ``t % depth``.  At
  tile 0 a prologue issues the copies for tiles ``0..depth-2``; every step
  then issues tile ``t+depth-1`` *before* waiting on tile ``t``'s
  semaphores, so the next gather is in flight while the current tile's dot
  runs.  ``depth=1`` degenerates to the synchronous issue-then-wait path.
  Reuse is safe because slot ``(t+depth-1) % depth`` last held tile
  ``t-1``, whose compute retired in the previous (sequential) grid step.
  Live tiles form a contiguous prefix of the table, so every issued copy is
  waited within the same inner tile loop — dead tiles still skip DMA
  entirely.
* ``fused`` — the pool carries the head-interleaved layout
  ``[K0,V0,K1,V1,...]`` (``kv_pages: (n_pages, page_size, 2*Hkv, hd)``), so
  a lane block holds each of its heads' K *and* V and ONE async copy per
  page feeds both operands: half the page-table reads and half the DMA
  issue count of the split layout.  The query sits in the K lanes and the
  output is read from the V lanes of the same tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def lane_block_heads(n_heads, head_dim, fused):
    """KV heads per DMA'd lane block of a flattened page row.

    A page row is ``Hkv*hd`` lanes (split) or ``2*Hkv*hd`` (fused: each
    head's ``[K|V]`` is ``2*hd`` lanes).  Mosaic only DMAs lane slices that
    are multiples of 128, so each kernel program fetches the narrowest run
    of whole heads that is: one head at hd 128, two at hd 64 (one K+V pair
    when fused).  A row that is no multiple of 128 lanes (the CPU tests'
    tiny configs) is taken whole, which only interpret mode runs."""
    unit = head_dim * (2 if fused else 1)
    for r in range(1, n_heads + 1):
        if n_heads % r == 0 and (r * unit) % 128 == 0:
            return r
    return n_heads


def place_heads(x, slot, n_slots):
    """Widen ``(..., hd)`` to ``(..., n_slots*hd)`` lanes with ``x`` in lane
    slot ``slot`` (broadcast over the leading dims) and zeros elsewhere: a
    zero-padded query dotted with a whole lane block scores only the keys
    in its own head's lanes."""
    hit = jnp.arange(n_slots) == slot[..., None]
    out = jnp.where(hit[..., None], x[..., None, :], 0)
    return out.reshape(*out.shape[:-2], n_slots * x.shape[-1])


def take_heads(o, slot, n_slots):
    """Inverse of ``place_heads``: keep lane slot ``slot`` of each row of a
    lane-block output (the other slots hold other heads' values)."""
    o = o.reshape(*o.shape[:-1], n_slots, o.shape[-1] // n_slots)
    hit = jnp.arange(n_slots) == slot[..., None]
    return jnp.where(hit[..., None], o, 0).sum(axis=-2).astype(o.dtype)


def _tile_copies(block_tables_ref, blk, t, slot, pools, tiles, sem, *,
                 page_size, pages_per_tile, width, b):
    """Async-copy descriptors gathering tile ``t``'s lane block ``blk`` into
    buffer ``slot``: one ``(page_size, width)`` copy per page and pool.

    The same descriptors are built twice — once to ``start()`` the DMAs,
    once to ``wait()`` them (a descriptor is just (src, dst, sem))."""
    lanes = pl.ds(pl.multiple_of(blk * width, width), width)
    out = []
    for j in range(pages_per_tile):
        pid = block_tables_ref[b, t * pages_per_tile + j]
        rows = pl.ds(j * page_size, page_size)
        for i, (hbm, tile) in enumerate(zip(pools, tiles)):
            out.append(pltpu.make_async_copy(
                hbm.at[pid, :, lanes], tile.at[slot, rows, :], sem.at[slot, i, j]
            ))
    return out


def _paged_decode_kernel(
    block_tables_ref,   # (B, n_tiles * pages_per_tile) scalar prefetch
    kv_len_ref,         # (B,) scalar prefetch
    q_ref,              # (rows, width) lane-placed queries of one lane block
    *refs,              # pools (1 fused | 2 split), o_ref, m, l, acc, tiles, sem
    page_size: int,
    pages_per_tile: int,
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
    blk = pl.program_id(1)
    tile_i = pl.program_id(2)
    tile = page_size * pages_per_tile

    kv_len = kv_len_ref[b]

    def live(t):
        # whole-tile skip: tiles past the valid length issue no DMA at all
        return t * tile < kv_len

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
        q = q_ref[...].astype(jnp.float32) * sm_scale     # (rows, width)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # (rows, tile)
        mask = k_pos[None, :] < kv_len
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


def _pad_tables(block_tables, pages_per_tile):
    """Right-pad the table columns to a tile multiple.  Pad entries use page
    id 0 — any valid id works: padded logical positions lie at or past
    ``max_pages * page_size >= kv_len`` and are masked (or whole-tile
    skipped) before they can contribute."""
    B, max_pages = block_tables.shape
    n_tiles = -(-max_pages // pages_per_tile)
    pad = n_tiles * pages_per_tile - max_pages
    if pad:
        block_tables = jnp.concatenate(
            [block_tables, jnp.zeros((B, pad), block_tables.dtype)], axis=1
        )
    return block_tables, n_tiles


def _kernel_scratch(depth, tile, width, rows, dtype, n_pools,
                    pages_per_tile):
    """Online-softmax (m, l, acc) carries plus ``n_pools`` multi-buffered
    K/V tiles and their DMA semaphores."""
    return [
        pltpu.VMEM((rows,), jnp.float32),
        pltpu.VMEM((rows,), jnp.float32),
        pltpu.VMEM((rows, width), jnp.float32),
        *[pltpu.VMEM((depth, tile, width), dtype)] * n_pools,
        pltpu.SemaphoreType.DMA((depth, n_pools, pages_per_tile)),
    ]


def flat_pools(pools, head_dim, fused):
    """Row-major ``(n_pages, ps, lanes)`` views of the page pools plus the
    kv head count and lane-block geometry ``(heads per block, width)``."""
    n_pages, page_size = pools[0].shape[:2]
    flat = tuple(p.reshape(n_pages, page_size, -1) for p in pools)
    unit = head_dim * (2 if fused else 1)
    n_kv = flat[0].shape[2] // unit
    r = lane_block_heads(n_kv, head_dim, fused)
    return flat, n_kv, r, r * unit


def _paged_decode_call(q, pools, block_tables, kv_lens, *, pages_per_tile,
                       buffering_depth, interpret, fused):
    B, Hq, hd = q.shape
    page_size = pools[0].shape[1]
    pools, Hkv, r, width = flat_pools(pools, hd, fused)
    assert Hq % Hkv == 0, (Hq, Hkv)
    assert buffering_depth >= 1, buffering_depth
    group = Hq // Hkv
    n_blk, rows = Hkv // r, r * group
    n_slots = width // hd

    block_tables, n_tiles = _pad_tables(
        block_tables.astype(jnp.int32), pages_per_tile
    )

    grid = (B, n_blk, n_tiles)
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size,
        pages_per_tile=pages_per_tile, sm_scale=1.0 / math.sqrt(hd),
        depth=buffering_depth, n_tiles=n_tiles, n_pools=len(pools),
        width=width,
    )

    # query row (j, g) of a block is q head (blk*r + j)*group + g: its K
    # lanes are slot j (split) or 2j (fused), its output the V slot after
    j_of_row = jnp.arange(rows) // group
    k_slot = j_of_row * (2 if fused else 1)
    v_slot = k_slot + (1 if fused else 0)
    q_b = place_heads(q.reshape(B, n_blk, rows, hd), k_slot, n_slots)

    tile = page_size * pages_per_tile
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, None, rows, width),
                    lambda b, h, ti, *_: (b, h, 0, 0),
                ),
                # K/V stay unblocked: the kernel gathers pages itself via
                # per-page async copies steered by the prefetched table
                *([pl.BlockSpec(memory_space=pl.ANY)] * len(pools)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, rows, width),
                lambda b, h, ti, *_: (b, h, 0, 0),
            ),
            scratch_shapes=_kernel_scratch(
                buffering_depth, tile, width, rows, pools[0].dtype,
                len(pools), pages_per_tile,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_blk, rows, width), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, kv_lens.astype(jnp.int32), q_b, *pools)

    return take_heads(out, v_slot, n_slots).reshape(B, Hq, hd)


@functools.partial(
    jax.jit, static_argnames=("pages_per_tile", "buffering_depth", "interpret")
)
def paged_decode_attention(
    q,              # (B, Hq, hd) one token per sequence
    k_pages,        # (n_pages, page_size, Hkv, hd) or (.., Hkv*hd) pool
    v_pages,        # same shape as k_pages
    block_tables,   # (B, max_pages) int32 physical page ids (pad: any valid id)
    kv_lens,        # (B,) int32 valid token counts
    *,
    pages_per_tile: int = 1,
    buffering_depth: int = 1,
    interpret: bool | None = None,
):
    return _paged_decode_call(
        q, (k_pages, v_pages), block_tables, kv_lens,
        pages_per_tile=pages_per_tile, buffering_depth=buffering_depth,
        interpret=interpret, fused=False,
    )


@functools.partial(
    jax.jit, static_argnames=("pages_per_tile", "buffering_depth", "interpret")
)
def paged_decode_attention_fused(
    q,              # (B, Hq, hd)
    kv_pages,       # (n_pages, page_size, 2*Hkv, hd) or (.., 2*Hkv*hd)
    block_tables,   # (B, max_pages) int32
    kv_lens,        # (B,) int32
    *,
    pages_per_tile: int = 1,
    buffering_depth: int = 1,
    interpret: bool | None = None,
):
    return _paged_decode_call(
        q, (kv_pages,), block_tables, kv_lens,
        pages_per_tile=pages_per_tile, buffering_depth=buffering_depth,
        interpret=interpret, fused=True,
    )
