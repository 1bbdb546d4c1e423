"""Pallas TPU kernels: KV page swap gather/scatter (KV migration).

Two subsystems move KV pages between device HBM and a host-side staging
buffer through these kernels: swap-out preemption (stage a victim's pages
instead of discarding them for recompute) and disaggregated prefill/decode
serving (export a finished prefill's KV from a prefill-pool replica, through
the host handoff store, into a decode-pool replica — gather on the source
device, scatter on the destination).  Either way the device half of the move
is pure data movement over the paged layout:

* ``swap_gather_pages`` — collect a victim's scattered physical pages into
  ONE contiguous staging tensor ``(L, n_pages, page_size, Hkv, hd)``; the
  engine starts ``copy_to_host_async`` on the result, so the host transfer
  is a single dense DMA rather than ``n_pages`` strided ones.
* ``swap_scatter_pages`` — the inverse: write a restored staging tensor into
  freshly allocated physical pages (aliased in place: the page pool is
  donated, no second copy of HBM is materialized).

Both ride the same scalar-prefetched page-id indirection as the paged
attention kernels: ids land in SMEM before the body runs, each grid step
moves one page with one async local copy.  The copy sees the page as
``(page_size, Hkv*hd)`` — a free row-major reshape of any trailing head
shape (and the layout the engine stores), whose lane width is a multiple of
128 at real widths where ``(Hkv, hd)`` with hd 64 is refused.  Page-id
lists are padded to power-of-two buckets by the caller (gather pads with the
sink page — garbage rows are sliced off host-side; scatter pads with the
sink page — duplicate writes land in the never-read sink).

The pure-jnp oracles (``use_pallas=False``) are the A/B reference: fancy
indexing for the gather, ``.at[].set`` for the scatter.  On CPU the kernels
run in interpret mode (correctness, not speed); on TPU the same calls
compile to Mosaic (``repro.kernels.interpret_mode``).

The INT8 host-tier variants (``*_q8``) fuse the quantization into the same
data movement: the gather DMAs each page into VMEM scratch, computes a
per-(layer, page, head) absmax scale on the fly — each head a run of hd
lanes of the flat page, its scale broadcast over those lanes — and writes an
int8 page plus its scales; the scatter dequantizes in VMEM before the async
copy into the (donated) physical pool.  The host round-trip then moves
~half the bytes, and the device pool never sees a quantized value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.ref import dequantize_pages, quantize_pages


def _gather_kernel(ids_ref, pages_ref, out_ref, sem):
    l = pl.program_id(0)
    i = pl.program_id(1)
    pid = ids_ref[i]
    cp = pltpu.make_async_copy(pages_ref.at[l, pid], out_ref.at[0, 0], sem)
    cp.start()
    cp.wait()


def _scatter_kernel(ids_ref, staged_ref, pages_in_ref, pages_out_ref, sem):
    del pages_in_ref                   # aliased with pages_out_ref (in-place)
    l = pl.program_id(0)
    i = pl.program_id(1)
    pid = ids_ref[i]
    cp = pltpu.make_async_copy(staged_ref.at[0, 0], pages_out_ref.at[l, pid], sem)
    cp.start()
    cp.wait()


def _flat(pages):
    """``(L, P, page_size, ...)`` -> row-major ``(L, P, page_size, lanes)``."""
    return pages.reshape(pages.shape[:3] + (-1,))


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def swap_gather_pages(pages, ids, *, use_pallas: bool = False,
                      interpret: bool | None = None):
    """Gather ``pages[:, ids]`` into a contiguous staging tensor.

    ``pages``: ``(L, n_phys, page_size, ...)`` physical pool — ``(Hkv, hd)``
    or the flat ``Hkv*hd`` the engine stores; ``ids``: ``(n,)`` int32 page
    ids (padded entries point at the sink page — the caller slices the
    staging tensor down to the real page count after the host copy
    drains).  Returns ``(L, n, page_size, ...)``.
    """
    if not use_pallas:
        return pages[:, ids]
    L = pages.shape[0]
    n = ids.shape[0]
    flat = _flat(pages)
    blk = (1, 1) + flat.shape[2:]
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, n),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(blk, lambda l, i, ids: (l, i, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((L, n) + flat.shape[2:], pages.dtype),
        interpret=resolve_interpret(interpret),
    )(ids, flat)
    return out.reshape((L, n) + pages.shape[2:])


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"),
                   donate_argnums=(0,))
def swap_scatter_pages(pages, ids, staged, *, use_pallas: bool = False,
                       interpret: bool | None = None):
    """Scatter a staging tensor back into physical pages:
    ``pages[:, ids] = staged``, in place (``pages`` is donated/aliased).

    Padded id entries must point at the sink page — duplicate scatter writes
    then land only in the never-read sink row.
    """
    if not use_pallas:
        return pages.at[:, ids].set(staged.astype(pages.dtype))
    L = pages.shape[0]
    n = ids.shape[0]
    flat = _flat(pages)
    blk = (1, 1) + flat.shape[2:]
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, n),
            in_specs=[
                pl.BlockSpec(blk, lambda l, i, ids: (l, i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct(flat.shape, pages.dtype),
        # alias indices count the scalar-prefetch operand: 0=ids, 1=staged,
        # 2=pages -> output 0
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(ids, _flat(staged.astype(pages.dtype)), flat)
    return out.reshape(pages.shape)


def _gather_q8_kernel(ids_ref, pages_ref, q_ref, scale_ref, scratch, sem, *,
                      head_dim):
    l = pl.program_id(0)
    i = pl.program_id(1)
    pid = ids_ref[i]
    cp = pltpu.make_async_copy(pages_ref.at[l, pid], scratch, sem)
    cp.start()
    cp.wait()
    x = scratch[...].astype(jnp.float32)                 # (page_size, lanes)
    col = jnp.max(jnp.abs(x), axis=0, keepdims=True)     # (1, lanes)
    head = jax.lax.broadcasted_iota(jnp.int32, col.shape, 1) // head_dim
    # per-head absmax broadcast over the head's lanes: one masked max per
    # head (a lane reshape to (H, hd) does not lower at hd 64)
    amax = jnp.zeros_like(col)
    for h in range(col.shape[1] // head_dim):
        in_h = head == h
        amax = jnp.where(
            in_h, jnp.max(jnp.where(in_h, col, 0.0), axis=1, keepdims=True),
            amax)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q_ref[0, 0] = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    scale_ref[0, 0] = scale


def _scatter_q8_kernel(ids_ref, q_ref, scale_ref, pages_in_ref,
                       pages_out_ref, scratch, sem):
    del pages_in_ref                   # aliased with pages_out_ref (in-place)
    l = pl.program_id(0)
    i = pl.program_id(1)
    pid = ids_ref[i]
    x = q_ref[0, 0].astype(jnp.float32) * scale_ref[0, 0]
    scratch[...] = x.astype(scratch.dtype)
    cp = pltpu.make_async_copy(scratch, pages_out_ref.at[l, pid], sem)
    cp.start()
    cp.wait()


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "use_pallas", "interpret"))
def swap_gather_pages_q8(pages, ids, *, head_dim: int | None = None,
                         use_pallas: bool = False,
                         interpret: bool | None = None):
    """Gather ``pages[:, ids]`` and quantize to INT8 in one pass.

    ``pages`` is ``(L, P, page_size, H, hd)``, or its flattening
    ``(L, P, page_size, H*hd)``, which needs ``head_dim=hd`` (a page row does
    not say where its heads end).  Same indirection and padding contract as
    ``swap_gather_pages``; returns ``(q, scales)``: int8
    ``(L, n, page_size, H, hd)`` staging pages plus f32 per-(layer, page,
    head) absmax scales ``(L, n, 1, H, 1)``.  The quantization happens in
    VMEM right after each page's DMA lands, so the host copy moves int8
    pages, never the full-width staging tensor.
    """
    if pages.ndim == 5:
        hd = pages.shape[-1]
        if head_dim not in (None, hd):
            raise ValueError(f"head_dim={head_dim} but the pool's heads "
                             f"are {hd} wide")
    elif head_dim is None:
        raise ValueError("a flat (L, P, page_size, H*hd) pool needs head_dim")
    else:
        hd = head_dim
    L, n, ps = pages.shape[0], ids.shape[0], pages.shape[2]
    if not use_pallas:
        return quantize_pages(pages[:, ids].reshape(L, n, ps, -1, hd))
    flat = _flat(pages)
    lanes = flat.shape[3]
    H = lanes // hd
    q, lane_scales = pl.pallas_call(
        functools.partial(_gather_q8_kernel, head_dim=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, n),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, 1, ps, lanes), lambda l, i, ids: (l, i, 0, 0)),
                pl.BlockSpec((1, 1, 1, lanes), lambda l, i, ids: (l, i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((ps, lanes), pages.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((L, n, ps, lanes), jnp.int8),
            jax.ShapeDtypeStruct((L, n, 1, lanes), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(ids, flat)
    scales = lane_scales.reshape(L, n, 1, H, hd)[..., :1]
    return q.reshape(L, n, ps, H, hd), scales


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"),
                   donate_argnums=(0,))
def swap_scatter_pages_q8(pages, ids, q_staged, scales, *,
                          use_pallas: bool = False,
                          interpret: bool | None = None):
    """Dequantize INT8 staging pages and scatter them into physical pages:
    ``pages[:, ids] = q_staged * scales``, in place (``pages`` donated).

    ``q_staged``/``scales`` are ``swap_gather_pages_q8``'s
    ``(L, n, page_size, H, hd)`` / ``(L, n, 1, H, 1)``; ``pages`` is the
    pool in either of its shapes.  Inverse of ``swap_gather_pages_q8`` —
    the dequant multiply runs in VMEM on each page before its async copy,
    so the device pool only ever holds full-width values.  Padding contract
    as ``swap_scatter_pages``.
    """
    if not use_pallas:
        deq = dequantize_pages(q_staged, scales, pages.dtype)
        return pages.at[:, ids].set(
            deq.reshape(deq.shape[:2] + pages.shape[2:]))
    L, n, ps = q_staged.shape[:3]
    flat = _flat(pages)
    lanes = flat.shape[3]
    lane_scales = jnp.broadcast_to(scales, (L, n, 1) + q_staged.shape[3:])
    out = pl.pallas_call(
        _scatter_q8_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, n),
            in_specs=[
                pl.BlockSpec((1, 1, ps, lanes), lambda l, i, ids: (l, i, 0, 0)),
                pl.BlockSpec((1, 1, 1, lanes), lambda l, i, ids: (l, i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((ps, lanes), pages.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(flat.shape, pages.dtype),
        # alias indices count the scalar-prefetch operand: 0=ids, 1=q,
        # 2=scales, 3=pages -> output 0
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret),
    )(ids, _flat(q_staged), _flat(lane_scales), flat)
    return out.reshape(pages.shape)
