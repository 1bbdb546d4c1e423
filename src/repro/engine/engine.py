"""Real-execution chunked-prefill engine: the paper's serving loop running
actual JAX forward passes (tiny models on CPU in the tests; ``chip_smoke.py``
runs it on a TPU at published widths).

Continuous batching with PAGED KV storage (vLLM layout, the default):
  * ``n_slots`` fixed *batch rows*; a request binds a slot at its FIRST
    scheduled chunk (late binding — queued or admission-delayed backlog pins
    nothing) and keeps it until it finishes or is preempted.
  * K/V live in a physical page pool ``(layers, n_blocks + 1, block_size,
    kv_heads * head_dim)`` (page rows flat: the layout a TPU keeps row-major
    and the paged kernels DMA as stored) whose page ids are exactly the
    ``KVBlockPool``'s block ids, addressed through per-slot block tables.
    Capacity scales with resident tokens, not ``n_slots x max_context``;
    prefix-cache hits need no payload copy (the matched blocks' pages are
    still resident); the last page is a write sink for padding lanes.
  * One jitted step per scheduling round executes the ENTIRE mixed batch —
    decode slots advance by 1 token, prefill slots by their scheduled chunk,
    idle slots by 0 — under static bucketed shapes; where it is cheaper
    (``SPLIT_ROW_COST``), a round with prefill chunks runs as one decode row
    per slot plus one row per chunk, not every slot padded to the widest
    chunk (``JAXEngine._stage``).  The step FUSES the cache-length update
    and token sampling (one dispatch per round, no follow-up host ops) and
    keeps the sampled tokens in a device-resident ``last_token`` buffer that
    the NEXT round's step consumes directly, so decode can proceed
    round-to-round without the host ever observing the token values.
  * PIPELINED serving (``EngineConfig(pipelined=True)``, the default):
    ``serve`` overlaps round N's device execution with the host's
    scheduling/aging/VTC/KV booking for round N+1.  The host readback of
    sampled ids becomes an async copy drained one round late and is used
    only for delivered outputs, length accounting, and preemption folds —
    greedy outputs are bit-identical to the synchronous engine
    (``pipelined=False``), which is kept for A/B.
  * The scheduler under test is the real ``repro.core`` code; latencies are
    wall-clock, so the LPRS predictor can be trained on real measurements.
  * Each engine lives on one device (``device``, default the first): its
    weights, page pool and per-round inputs are placed there, so a fleet can
    put one replica on each chip of a host.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.request import Request, RequestState
from repro.core.scheduler import ChunkedPrefillScheduler, ScheduledBatch
from repro.engine.costmodel import CostModel, CostModelConfig
from repro.engine.kv_cache import KVBlockPool, KVPoolConfig, PAGED_RESIDENT
from repro.engine.metrics import (
    LatencyReport, MemoryReport, RobustnessReport, SLOReport, summarize,
    summarize_memory, summarize_robustness, summarize_slo,
)
from repro.kernels.ops import (
    gather_swap_pages, gather_swap_pages_q8, scatter_swap_pages,
    scatter_swap_pages_q8,
)
from repro.engine.sampler import SamplerConfig, sample_tokens
from repro.engine.trace import Recorder, host_bubbles_ms
from repro.models.model import Model, build_model
from repro.robustness import FailoverStats, ReplicaHealth

# What one decode row of a split round costs, in query positions of the
# padded round's prefill attention, per attention path.  A round with
# prefill chunks at bucket C runs split -- one decode row per slot plus P
# rows of C tokens -- only where the padding it drops costs more than the
# decode rows it adds: (n_slots - P) * C > SPLIT_ROW_COST * n_slots.  From
# step times on TPU v5e, 16 slots of 4k context (PERF.md section 5): the
# oracle's decode rows pay float32 copies of the gathered K/V (any cost
# from 128 to 191 picks the faster kind at every shape timed); with the
# Pallas kernels the split was faster at every shape timed.
SPLIT_ROW_COST = {"oracle": 160, "pallas": 0}


@dataclass
class EngineConfig:
    n_slots: int = 16
    max_context: int = 1024
    chunk_buckets: Tuple[int, ...] = (1, 16, 32, 64, 128, 256)
    use_pallas: bool = False          # True: Pallas kernels (interpret on CPU)
    paged_kv: bool = True             # block-table pages; False = dense slots
    kv_block_size: int = 16           # page size when the engine owns its pool
    pages_per_tile: int = 1           # pages DMA-gathered per paged-kernel tile
    # physical page-pool layout: "split" keeps separate K and V pools;
    # "fused" interleaves them on the head axis ([K0,V0,K1,V1,...]) so the
    # paged kernels fetch each page's K+V with ONE DMA (half the page-table
    # reads and issue count).  Paged-kv only.
    kv_layout: str = "split"
    # VMEM tile buffers per paged-kernel grid: tile t+depth-1's gather is
    # issued before tile t's wait, so DMA overlaps the MXU dot (1 = the
    # synchronous issue-then-wait path)
    buffering_depth: int = 1
    pipelined: bool = True            # overlap schedule(N+1) with execute(N)
    # preemption mode: "recompute" discards a victim's KV (re-prefill from
    # scratch, the A/B default); "swap" stages it host-side and restores it
    # on re-schedule — the scheduler picks per victim via the cost model
    preemption_mode: str = "recompute"
    # numerics quarantine: the fused step additionally emits a per-slot
    # all-finite mask over the logits (one extra readback lane, no extra
    # dispatch); the serve loop sheds requests whose sampled logits went
    # NaN/Inf (shed_reason="numerics") instead of streaming garbage ids
    nan_guard: bool = False
    sampler: SamplerConfig = field(default_factory=SamplerConfig)
    seed: int = 0


def _pow2_bucket(n: int) -> int:
    """Smallest power of two >= n: the dirty-row block-table scatter pads its
    row count to these buckets so only O(log n_slots) shapes ever compile
    (warmup pre-compiles exactly this set)."""
    k = 1
    while k < n:
        k <<= 1
    return k


@dataclass
class InflightRound:
    """One dispatched-but-undrained round: the device is executing (or has
    finished) it while the host schedules the next one.  ``toks`` is the
    device array of sampled ids; ``sampled`` names the (request, slot) pairs
    whose token this round actually produced (decodes + prefill-completing
    chunks).  ``out_index`` records, per request, which position of
    ``output_tokens`` received this round's placeholder (filled by the serve
    loop after ``on_batch_done``); ``drain`` patches the real ids there."""
    toks: jax.Array
    sampled: List[Tuple[Request, int]]
    t_dispatch: float
    out_index: Dict[int, int] = field(default_factory=dict)
    finished: List[Request] = field(default_factory=list)
    prefill_ids: set = field(default_factory=set)   # this round's prefill reqs
    # nan_guard: per-slot all-finite logits mask (async readback alongside
    # toks); drain fills nonfinite with the sampled req_ids whose logits
    # carried NaN/Inf so the serve loop can quarantine them
    finite: Optional[jax.Array] = None
    nonfinite: set = field(default_factory=set)
    # the batch this round executed — a crash unwind enumerates its members
    batch: Optional[ScheduledBatch] = None


class JAXEngine:
    """Executes ScheduledBatches with real forward passes."""

    def __init__(self, model_cfg: ModelConfig, cfg: Optional[EngineConfig] = None,
                 params=None, kv_pool: Optional[KVBlockPool] = None,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg or EngineConfig()
        self.model_cfg = model_cfg
        self.model: Model = build_model(model_cfg)
        self.device = device or jax.devices()[0]
        rng = jax.random.PRNGKey(self.cfg.seed)
        # a replica on another device gets its own copy of shared weights
        self.params = self._put(
            params if params is not None else self.model.init(rng))
        self._rng = self._put(jax.random.PRNGKey(self.cfg.seed + 1))

        B = self.cfg.n_slots
        self.slot_of: Dict[int, int] = {}          # req_id -> slot
        self.free_slots = list(range(B - 1, -1, -1))

        # host spans and round counters; a ReplicaServer hands in its own
        self.trace = Recorder()
        # (C, P) of the split rounds: P prefill rows, a power of two under
        # the slot count, where the padding dropped outweighs the decode
        # rows added (SPLIT_ROW_COST); the dense path always runs padded
        row_cost = SPLIT_ROW_COST["pallas" if self.cfg.use_pallas else "oracle"]
        self._split_shapes = frozenset(
            (C, P) for C in self.cfg.chunk_buckets if C > 1
            for P in (1 << i for i in range(B.bit_length()))
            if self.cfg.paged_kv and (B - P) * C > row_cost * B)
        # nan_guard: req_ids whose sampled logits were non-finite in the most
        # recently drained round (sync serve loops read it after execute())
        self.last_nonfinite: set = set()
        # storage poisoned by the nan_logits chaos site.  Pages/slots released
        # by the quarantined victim go back to the free pool still holding
        # NaN, so a later request reusing them would read non-finite lanes it
        # never wrote — scrub_poisoned() zeroes them once the victim is shed.
        self._poisoned: List[tuple] = []

        # swap-out preemption: device->host gathers whose async host copy has
        # not drained yet — (req_id, staging record, per-cache-tensor
        # arrays); finalize_swaps() attaches the payload to the record
        # DIRECTLY (not through the pool), so a record the disagg router
        # prefetched into the handoff store or a destination pool still
        # finalizes — same one-round-late path as the sampled-token readback
        self._pending_swaps: List[Tuple[int, object, Tuple[jax.Array, ...]]] = []

        self.kv_pool: Optional[KVBlockPool] = kv_pool
        # warmup() flips this: binding a shape-changing pool afterwards would
        # silently invalidate every compiled shape, so bind_kv_pool refuses
        self.warmed = False
        # the engine books blocks itself only while it owns a private pool;
        # an externally bound pool is booked by the scheduler
        self._owns_pool = False
        if self.cfg.paged_kv and self.kv_pool is None:
            bs = self.cfg.kv_block_size
            per_slot = math.ceil(self.cfg.max_context / bs) + 1
            self.kv_pool = KVBlockPool(KVPoolConfig(
                n_blocks=B * per_slot, block_size=bs,
            ))
            self._owns_pool = True
        self._build_state()

    def _put(self, x):
        """Place host data (or another device's arrays) on this engine's
        device."""
        return jax.device_put(x, self.device)

    # -- physical KV layout ----------------------------------------------------
    def _build_state(self) -> None:
        cfg, model_cfg = self.cfg, self.model_cfg
        B, S = cfg.n_slots, cfg.max_context
        hd = model_cfg.resolved_head_dim
        dt = jnp.dtype(model_cfg.param_dtype)
        impl = self.model.impl
        use_pallas = cfg.use_pallas
        pages_per_tile = cfg.pages_per_tile
        assert cfg.kv_layout in ("split", "fused"), cfg.kv_layout
        assert cfg.buffering_depth >= 1, cfg.buffering_depth
        self._fused = cfg.paged_kv and cfg.kv_layout == "fused"
        assert self._fused or cfg.kv_layout == "split", (
            "kv_layout='fused' requires paged_kv=True"
        )

        def _inject_last(tokens, use_last, last_token):
            """Decode lanes consume the device-resident last sampled token
            (the host staged a 0 there — it may not know the id yet)."""
            col0 = jnp.arange(tokens.shape[1])[None, :] == 0
            return jnp.where(use_last[:, None] & col0,
                             last_token[:, None], tokens)

        def _fused_tail(logits, cache, lens, chunk_lens, last_token,
                        sample_mask, rng):
            """Sampling + length update + device token feedback, fused into
            the SAME dispatch as the forward pass (no follow-up host ops)."""
            with jax.named_scope("sample"):
                toks = sample_tokens(logits, rng, self.cfg.sampler)
                new_last = jnp.where(sample_mask, toks, last_token)
                if cfg.nan_guard:
                    finite = jnp.isfinite(logits).all(axis=-1)
                    return toks, cache, lens + chunk_lens, new_last, finite
                return toks, cache, lens + chunk_lens, new_last

        if cfg.paged_kv:
            bs = self.kv_pool.cfg.block_size
            # physical pages = pool blocks + 1 trailing sink page (padding
            # lanes scatter there; block tables also pad with it)
            self._n_phys = self.kv_pool.cfg.n_blocks + 1
            self._sink = self.kv_pool.cfg.n_blocks
            self.max_pages = math.ceil(S / bs) + 1
            n_kv = model_cfg.n_kv_heads * (2 if self._fused else 1)
            kv_shape = (model_cfg.n_layers, self._n_phys, bs, n_kv * hd)
            # device-resident block tables, refreshed with DIRTY-SLOT
            # incremental updates; _bt_host mirrors exactly what the device
            # holds, _bt_len tracks per-slot entries already uploaded
            self._bt_host = np.full((B, self.max_pages), self._sink, np.int32)
            self._bt_len = np.zeros((B,), np.int32)
            self._bt_dirty: set = set()
            self.block_tables = self._put(self._bt_host)

            def step(params, tokens, cache, lens, chunk_lens, block_tables,
                     last_token, use_last, sample_mask, rng,
                     pre_tokens=None, pre_slot=None):
                tokens = _inject_last(tokens, use_last, last_token)
                logits, cache = impl.chunked_step_paged(
                    params, tokens, cache, lens, chunk_lens, block_tables,
                    pre_tokens, pre_slot,
                    use_pallas=use_pallas, pages_per_tile=pages_per_tile,
                    kv_layout=cfg.kv_layout,
                    buffering_depth=cfg.buffering_depth,
                )
                return _fused_tail(logits, cache, lens, chunk_lens,
                                   last_token, sample_mask, rng)

            donate = (2, 3, 6)     # cache, lens, last_token
        else:
            kv_shape = (model_cfg.n_layers, B, S + 1, model_cfg.n_kv_heads, hd)
            self.block_tables = None

            def step(params, tokens, cache, lens, chunk_lens,
                     last_token, use_last, sample_mask, rng):
                tokens = _inject_last(tokens, use_last, last_token)
                logits, cache = impl.chunked_step(
                    params, tokens, cache, lens, chunk_lens, use_pallas=use_pallas
                )
                return _fused_tail(logits, cache, lens, chunk_lens,
                                   last_token, sample_mask, rng)

            donate = (2, 3, 5)     # cache, lens, last_token

        dev = self.device
        self.cache = {nm: jnp.zeros(kv_shape, dt, device=dev)
                      for nm in self._cache_names()}
        self.lens = jnp.zeros((B,), jnp.int32, device=dev)
        self.last_token = jnp.zeros((B,), jnp.int32, device=dev)
        self._step = jax.jit(step, donate_argnums=donate)

    def bind_kv_pool(self, kv_pool: Optional[KVBlockPool]) -> None:
        """Adopt the serve loop's shared pool: the physical page array is
        rebuilt so page ids == the pool's block ids (the scheduler books
        blocks; the engine just follows the tables).  Must happen before any
        request is in flight."""
        if kv_pool is None or kv_pool is self.kv_pool:
            return
        assert not self.slot_of, "cannot rebind the KV pool mid-flight"
        if self.warmed and self.cfg.paged_kv:
            # the paged rebuild resizes the physical page array (page ids ==
            # block ids), so every shape warmup compiled is stale — the run
            # would silently re-pay cold compilation inside serving rounds
            raise RuntimeError(
                "bind_kv_pool after warmup(): the paged rebuild invalidates "
                "every prewarmed shape — bind the external pool FIRST, then "
                "call warmup()"
            )
        if kv_pool.cfg.host_kv_dtype == "int8" and not self.cfg.paged_kv:
            raise RuntimeError(
                "host_kv_dtype='int8' requires paged_kv: the quantized swap "
                "kernels are page-shaped"
            )
        self.kv_pool = kv_pool
        self._owns_pool = False
        if self.cfg.paged_kv:
            self._build_state()

    def _cache_names(self) -> Tuple[str, ...]:
        """The cache dict's tensor keys, in swap payload order: the fused
        layout stores ONE head-interleaved pool, split stores two."""
        return ("kv",) if self._fused else ("k", "v")

    def warmup(self, *, include_swap: Optional[bool] = None) -> None:
        """Compile every bucket shape once so profiling sees steady-state
        latencies, not jit compilation (the paper's 'cleaned' samples).

        Every jitted shape the serving loop can hit under the CONFIGURED
        ``(kv_layout, buffering_depth, pages_per_tile)`` combination is
        covered: the step compiles per ``round_shapes()`` entry with those
        knobs baked in, the dirty-row block-table scatter per power-of-two
        row bucket, and — when this engine can swap (``preemption_mode="swap"``) or the
        caller says it will export/import KV (``include_swap=True``, the
        disagg handoff path, which rides the same gather/scatter kernels
        regardless of preemption mode) — the swap kernels per page-id
        bucket.

        Order matters with an EXTERNAL pool: ``bind_kv_pool`` rebuilds the
        physical page array (page ids must equal the pool's block ids),
        which changes the cache shape and invalidates everything compiled
        here — bind first, then warm up."""
        B = self.cfg.n_slots
        for C, P in self.round_shapes():
            self._rng, sub = jax.random.split(self._rng)
            out = self._step(*self._dummy_round(C, sub, P))
            toks, self.cache, self.lens, self.last_token = out[:4]
            jax.block_until_ready(toks)
        # reset cache/lens state touched by the dummy rounds (paged writes all
        # land in the sink page, which is never read back)
        self.lens = jnp.zeros((B,), jnp.int32, device=self.device)
        if self.cfg.paged_kv:
            # pre-compile every dirty-row scatter bucket the runtime can hit
            # (slot 0's current mirror row rewritten in place — a data no-op)
            for k in sorted({_pow2_bucket(n) for n in range(1, B + 1)}):
                idx = np.zeros((k,), np.int32)
                self.block_tables = self.block_tables.at[self._put(idx)].set(
                    self._put(self._bt_host[idx])
                )
            jax.block_until_ready(self.block_tables)
        if include_swap is None:
            include_swap = self.cfg.preemption_mode == "swap"
        if include_swap:
            self._prewarm_swap_shapes()
        self.warmed = True

    def round_shapes(self) -> List[Tuple[int, int]]:
        """``(C, P)`` of every step shape a round can take: ``P = 0`` is one
        row of ``C`` tokens per slot (decode-only rounds at ``C = 1``),
        ``P > 0`` a split round of one decode row per slot and ``P`` prefill
        rows of ``C`` tokens."""
        return sorted({(C, 0) for C in self.cfg.chunk_buckets}
                      | self._split_shapes)

    def _dummy_round(self, C: int, rng, P: int = 0):
        """Step arguments of a throwaway round of shape ``(C, P)``: slot 0
        takes one token (paged: into the sink page), every other slot and
        every prefill row idles, and no sampled token is kept."""
        B = self.cfg.n_slots
        off = self._put(np.zeros((B,), np.bool_))
        chunk_lens = np.zeros((B,), np.int32)
        chunk_lens[0] = 1
        args = (self.params, self._put(np.ones((B, 1 if P else C), np.int32)),
                self.cache, self.lens, self._put(chunk_lens))
        if self.cfg.paged_kv:
            args += (self.block_tables,)
        args += (self.last_token, off, off, rng)
        if P:
            args += (self._put(np.ones((P, C), np.int32)),
                     self._put(np.full((P,), B, np.int32)))
        return args

    def step_hlo(self, C: int, P: int = 0) -> str:
        """Compiled HLO text of the round step of shape ``(C, P)``.  On a
        TPU, Mosaic-compiled Pallas kernels show up as ``tpu_custom_call``;
        interpreted ones would not."""
        args = self._dummy_round(C, self._rng, P)
        return self._step.lower(*args).compile().as_text()

    def _prewarm_swap_shapes(self) -> None:
        """Compile the swap gather/scatter for every page-id bucket a swap
        can hit (paged) or the slot row copy (dense), so the first real
        preemption — or disagg handoff export/import — doesn't pay jit
        compilation inside a serving round."""
        names = self._cache_names()
        if self.cfg.paged_kv:
            buckets = sorted({_pow2_bucket(n)
                              for n in range(1, self.max_pages + 1)})
            q8 = self._host_quantized()
            hd = self.model_cfg.resolved_head_dim
            for k in buckets:
                ids = self._put(np.full((k,), self._sink, np.int32))  # no-op
                for nm in names:
                    if q8:
                        q, scales = gather_swap_pages_q8(
                            self.cache[nm], ids, head_dim=hd,
                            use_pallas=self.cfg.use_pallas)
                        self.cache[nm] = scatter_swap_pages_q8(
                            self.cache[nm], ids, q, scales,
                            use_pallas=self.cfg.use_pallas)
                    else:
                        staged = gather_swap_pages(
                            self.cache[nm], ids,
                            use_pallas=self.cfg.use_pallas)
                        self.cache[nm] = scatter_swap_pages(
                            self.cache[nm], ids, staged,
                            use_pallas=self.cfg.use_pallas)
            jax.block_until_ready(self.cache[names[0]])
        else:
            k_row = np.asarray(self.cache["k"][:, 0])
            self.cache["k"] = self.cache["k"].at[:, 0].set(self._put(k_row))
            jax.block_until_ready(self.cache["k"])

    # -- slot management -------------------------------------------------------
    def acquire_slot(self, req: Request) -> bool:
        """Late slot binding: called by the scheduler when it first commits a
        chunk for ``req`` (NOT at admission — queued or rate-limit-delayed
        backlog pins no slot).  Returns True when the request holds a slot
        after the call.

        The prefix-cache lookup also happens HERE, not at admission: a
        parked backlog must not pin cached blocks (refcounts) or tenant
        quota it cannot use yet.  Only restorable blocks count — host-side
        payloads (dense) or still-resident pages (paged).  On a hit the
        dense layout copies the matched payloads into the fresh slot; the
        paged layout's matched pages are already resident (zero-copy)."""
        if req.req_id in self.slot_of:
            return True
        if not self.free_slots:
            return False
        slot = self.free_slots.pop()
        self.slot_of[req.req_id] = slot
        if (self.kv_pool is not None and req.prefill_done == 0
                and not self.kv_pool.tables.get(req.req_id)):
            matched = self.kv_pool.match_prefix(req.req_id, require_payload=True)
            if matched > 0:
                req.prefill_done = matched
        self.lens = self.lens.at[slot].set(req.prefill_done)
        if self.cfg.paged_kv:
            self._bt_host[slot, :] = self._sink
            self._bt_len[slot] = 0
            self._bt_dirty.add(slot)
        elif req.prefill_done > 0 and self.kv_pool is not None:
            self._restore_prefix_dense(req, slot)
        return True

    def release(self, req: Request) -> None:
        """Drop the request's slot (finish or preemption).  Idempotent.  With
        an engine-owned pool the request's blocks go back too."""
        slot = self.slot_of.pop(req.req_id, None)
        if slot is not None:
            self.free_slots.append(slot)
            if self.cfg.paged_kv:
                self._bt_host[slot, :] = self._sink
                self._bt_len[slot] = 0
                self._bt_dirty.add(slot)
        if self._owns_pool:
            self.kv_pool.release(req.req_id)

    def has_capacity(self) -> bool:
        return len(self.free_slots) > 0

    # -- swap-out preemption (device<->host KV migration) ----------------------
    def _host_quantized(self) -> bool:
        """True when staged host pages are INT8 (pool ``host_kv_dtype``)."""
        return (self.kv_pool is not None
                and self.kv_pool.cfg.host_kv_dtype == "int8")

    def _swap_page_ids(self, req_id: int) -> Tuple[np.ndarray, int]:
        """The request's physical page ids, right-padded with the sink page
        to a power-of-two bucket so the gather/scatter kernels only ever
        compile O(log max_pages) shapes.  Returns (padded ids, real count)."""
        table = self.kv_pool.tables.get(req_id, [])
        n = len(table)
        k = _pow2_bucket(max(n, 1))
        ids = np.full((k,), self._sink, np.int32)
        ids[:n] = table
        return ids, n

    def swap_out(self, req: Request) -> None:
        """Scheduler swapper hook: gather the victim's KV into a contiguous
        staging tensor (paged: one jitted page gather over its block table;
        dense: its slot rows), start the async device→host copy, move the
        pool accounting to a SWAPPING staging record, and release the slot.
        The payload becomes restorable only when ``finalize_swaps`` drains
        the copy — the same one-round-late visibility the token readback
        has, so a mid-pipeline victim is never restored (or re-bound) in the
        round that is still copying its pages out."""
        with self.trace.span("swap_out", req.req_id):
            self._swap_out(req)

    def _swap_out(self, req: Request) -> None:
        pool = self.kv_pool
        slot = self.slot_of.get(req.req_id)
        assert slot is not None, f"swap_out of unbound req {req.req_id}"
        if self.cfg.paged_kv:
            ids, _n = self._swap_page_ids(req.req_id)
            jids = self._put(ids)
            if self._host_quantized():
                # fused gather+quantize: the host copy moves int8 pages plus
                # small per-page-per-head scales — about half the bytes
                arrays = tuple(
                    gather_swap_pages_q8(
                        self.cache[nm], jids,
                        head_dim=self.model_cfg.resolved_head_dim,
                        use_pallas=self.cfg.use_pallas)
                    for nm in self._cache_names()
                )
            else:
                arrays = tuple(
                    gather_swap_pages(self.cache[nm], jids,
                                      use_pallas=self.cfg.use_pallas)
                    for nm in self._cache_names()
                )
        else:
            # dense layout: the whole slot row (static shape — positions past
            # the stored length are never attended to after restore)
            arrays = (self.cache["k"][:, slot], self.cache["v"][:, slot])
        for a in jax.tree_util.tree_leaves(arrays):
            a.copy_to_host_async()
        # keep the RECORD, not just the id: finalize must find it wherever
        # the disagg router's prefetch may have moved it by drain time
        rec = pool.swap_out(req.req_id)        # state: SWAPPING
        self._pending_swaps.append((req.req_id, rec, arrays))
        self.release(req)

    def finalize_swaps(self) -> None:
        """Drain pending swap-out copies: block until each staged tensor is
        host-side (the copies were dispatched before the current round's
        step, so this wait is bounded) and mark the staging records
        SWAPPED_OUT.  The payload attaches to the record object itself —
        location-transparent: under handoff PREFETCH the record may already
        sit in the ``KVHandoffStore`` or a destination pool's staging store
        rather than this engine's pool.  Called from ``drain`` — swap
        traffic retires on the same one-round-late path as sampled tokens —
        and by the serve loop when no round is in flight to piggyback on."""
        if not self._pending_swaps:
            return
        for _req_id, rec, arrays in self._pending_swaps:
            KVBlockPool.finalize_record(
                rec, jax.tree_util.tree_map(np.asarray, arrays)
            )
        self._pending_swaps.clear()

    def has_pending_swaps(self) -> bool:
        return bool(self._pending_swaps)

    def swap_in(self, req: Request, payload) -> None:
        """Scheduler restorer hook, called right after ``pool.swap_in``
        rebuilt the request's table from fresh blocks: scatter the staged
        K/V into the new physical pages (paged) or the freshly bound slot's
        rows (dense) and restore the device-side length."""
        with self.trace.span("swap_in", req.req_id):
            self._swap_in(req, payload)

    def _swap_in(self, req: Request, payload) -> None:
        slot = self.slot_of.get(req.req_id)
        assert slot is not None, f"swap_in of unbound req {req.req_id}"
        assert payload is not None, f"swap_in of req {req.req_id} without payload"
        names = self._cache_names()
        assert len(payload) == len(names), (
            f"req {req.req_id}: payload arity {len(payload)} != cache layout "
            f"{names} — swapped under a different kv_layout?"
        )
        tokens = self.kv_pool.lens.get(req.req_id, 0)
        if self.cfg.paged_kv:
            ids, n = self._swap_page_ids(req.req_id)
            staged_pages = (payload[0][0] if isinstance(payload[0], tuple)
                            else payload[0]).shape[1]
            assert n and ids.shape[0] == staged_pages, (
                f"req {req.req_id}: restore bucket {ids.shape[0]} != staged "
                f"{staged_pages}"
            )
            jids = self._put(ids)
            for nm, a in zip(names, payload):
                self._scatter_staged(nm, jids, a)
            # table changed wholesale: force a full device row rewrite
            self._bt_host[slot, :] = self._sink
            self._bt_len[slot] = 0
            self._bt_dirty.add(slot)
        else:
            for nm, a in zip(names, payload):
                self.cache[nm] = self.cache[nm].at[:, slot].set(self._put(a))
        self.lens = self.lens.at[slot].set(tokens)

    def _scatter_staged(self, nm: str, jids, staged) -> None:
        """Scatter one cache tensor's staged pages — a ``(q, scales)`` pair
        rides the fused dequantizing scatter, a plain array the fp one."""
        if isinstance(staged, tuple):
            q, scales = staged
            self.cache[nm] = scatter_swap_pages_q8(
                self.cache[nm], jids, self._put(q), self._put(scales),
                use_pallas=self.cfg.use_pallas)
        else:
            self.cache[nm] = scatter_swap_pages(
                self.cache[nm], jids, self._put(staged),
                use_pallas=self.cfg.use_pallas)

    @staticmethod
    def slice_swap_payload(payload, tail_start_blocks: int, n_blocks: int):
        """Trim a host-staged payload to its tail pages (partial swap-in):
        keep pages ``[tail_start_blocks, n_blocks)`` of every staged array
        — page axis 1, real pages only; the pow2 padding is rebuilt for the
        tail's own scatter bucket (padded entries target the sink page, so
        their content is never read).  Returns real copies: the prefix pages'
        memory is actually released once the original payload drops."""
        k = n_blocks - tail_start_blocks
        kpad = _pow2_bucket(max(k, 1))

        def trim(a):
            a = np.asarray(a)
            out = np.zeros(a.shape[:1] + (kpad,) + a.shape[2:], a.dtype)
            out[:, :k] = a[:, tail_start_blocks:n_blocks]
            return out

        return tuple(
            tuple(trim(x) for x in a) if isinstance(a, tuple) else trim(a)
            for a in payload
        )

    def swap_in_tail(self, req: Request, payload,
                     tail_start_blocks: int) -> None:
        """Scheduler tail-restorer hook, called right after
        ``pool.swap_in_tail`` appended fresh blocks for the staged tail: the
        request re-prefilled blocks ``[0, tail_start_blocks)`` normally, so
        only the tail pages are scattered and the device length jumps to the
        record's full stored length."""
        slot = self.slot_of.get(req.req_id)
        assert slot is not None, f"swap_in_tail of unbound req {req.req_id}"
        assert payload is not None, (
            f"swap_in_tail of req {req.req_id} without payload"
        )
        assert self.cfg.paged_kv, "partial swap-in requires the paged layout"
        names = self._cache_names()
        assert len(payload) == len(names), (
            f"req {req.req_id}: payload arity {len(payload)} != cache layout "
            f"{names} — swapped under a different kv_layout?"
        )
        table = self.kv_pool.tables.get(req.req_id, [])
        tail = table[tail_start_blocks:]
        assert tail, f"req {req.req_id}: empty tail restore"
        kpad = _pow2_bucket(len(tail))
        ids = np.full((kpad,), self._sink, np.int32)
        ids[: len(tail)] = tail
        staged_pages = (payload[0][0] if isinstance(payload[0], tuple)
                        else payload[0]).shape[1]
        assert kpad == staged_pages, (
            f"req {req.req_id}: tail bucket {kpad} != staged {staged_pages}"
        )
        jids = self._put(ids)
        for nm, a in zip(names, payload):
            self._scatter_staged(nm, jids, a)
        tokens = self.kv_pool.lens.get(req.req_id, 0)
        self._bt_host[slot, :] = self._sink
        self._bt_len[slot] = 0
        self._bt_dirty.add(slot)
        self.lens = self.lens.at[slot].set(tokens)

    def poison_kv(self, req: Request) -> None:
        """Chaos hook (the ``nan_logits`` fault site): corrupt the request's
        OWN attended KV so its next forward pass yields non-finite logits,
        exercising the numerics-quarantine path end to end.  Only PRIVATE
        storage is touched — shared prefix pages (refcount > 1) are skipped,
        so co-resident requests stay bit-identical to a fault-free run."""
        slot = self.slot_of.get(req.req_id)
        if slot is None:
            return
        written = int(jax.device_get(self.lens)[slot])
        if written <= 0:
            return
        if self.cfg.paged_kv:
            table = self.kv_pool.tables.get(req.req_id, [])
            if not table:
                return
            bs = self.kv_pool.cfg.block_size
            bi = min((written - 1) // bs, len(table) - 1)
            while bi >= 0 and self.kv_pool._ref.get(table[bi], 1) > 1:
                bi -= 1
            if bi < 0:
                return           # every page is shared: nothing safe to poison
            pid = table[bi]
            for nm in self._cache_names():
                self.cache[nm] = self.cache[nm].at[:, pid].set(jnp.nan)
            self._poisoned.append(("page", pid))
        else:
            for nm in ("k", "v"):
                self.cache[nm] = (
                    self.cache[nm].at[:, slot, written - 1].set(jnp.nan)
                )
            self._poisoned.append(("dense", slot, written - 1))

    def scrub_poisoned(self) -> None:
        """Zero the storage poison_kv() corrupted.  Called once the victim is
        quarantined: its pages return to the free pool, and a NaN lane the
        next owner never overwrites must not re-trigger the guard on it."""
        for entry in self._poisoned:
            if entry[0] == "page":
                for nm in self._cache_names():
                    self.cache[nm] = self.cache[nm].at[:, entry[1]].set(0)
            else:
                for nm in ("k", "v"):
                    self.cache[nm] = (
                        self.cache[nm].at[:, entry[1], entry[2]].set(0)
                    )
        self._poisoned.clear()

    # -- prefix-cache payloads -------------------------------------------------
    def _restore_prefix_dense(self, req: Request, slot: int) -> None:
        """Dense layout only: copy a prefix-cache hit's stored K/V payloads
        into the request's slot so the skipped prefill positions hold
        numerically identical state (causal attention: prefix KV depends only
        on prefix tokens).  At bind time ``prefill_done`` is exactly the
        matched token count."""
        kv_pool = self.kv_pool
        bs = kv_pool.cfg.block_size
        table = kv_pool.tables.get(req.req_id, [])
        n_matched = req.prefill_done // bs
        ks, vs = [], []
        for bid in table[:n_matched]:
            payload = kv_pool.payload(bid)
            assert payload is not None and payload is not PAGED_RESIDENT, (
                "dense engine prefix match requires host-side payloads"
            )
            ks.append(payload[0])
            vs.append(payload[1])
        if ks:
            # one functional update per cache tensor, not one per block
            self.cache["k"] = (
                self.cache["k"].at[:, slot, : n_matched * bs].set(jnp.concatenate(ks, axis=1))
            )
            self.cache["v"] = (
                self.cache["v"].at[:, slot, : n_matched * bs].set(jnp.concatenate(vs, axis=1))
            )

    def capture_sealed(self, req: Request) -> None:
        """Make newly sealed (full, content-addressed) prompt blocks
        restorable by future prefix hits.  Dense layout: park the K/V arrays
        (slices of the round's output cache — an async device computation, no
        host sync even mid-pipeline).  Paged layout: the data already lives
        at the block's physical page — a residency marker suffices, no
        copy."""
        kv_pool = self.kv_pool
        if kv_pool is None:
            return
        if self.cfg.paged_kv:
            for _idx, bid, _s, _e in kv_pool.take_newly_sealed(req.req_id):
                kv_pool.store_payload(bid, PAGED_RESIDENT)
            return
        slot = self.slot_of.get(req.req_id)
        if slot is None:
            return
        for _idx, bid, s, e in kv_pool.take_newly_sealed(req.req_id):
            k_blk = jnp.asarray(self.cache["k"][:, slot, s:e])
            v_blk = jnp.asarray(self.cache["v"][:, slot, s:e])
            kv_pool.store_payload(bid, (k_blk, v_blk))

    # -- one round ---------------------------------------------------------------
    def _bucket(self, c: int) -> int:
        for b in self.cfg.chunk_buckets:
            if c <= b:
                return b
        return self.cfg.chunk_buckets[-1]

    def _sync_block_tables(self, batch: ScheduledBatch) -> None:
        """Refresh scheduled requests' device block-table rows from the pool
        with DIRTY-SLOT granularity: per-request tables only ever APPEND
        between binds, so each row uploads only when it changed (new page
        crossed, fresh bind, release) — one ``.at[slots].set`` over the dirty
        rows instead of re-uploading the whole (B, max_pages) table every
        round."""
        pool = self.kv_pool
        if self._owns_pool:
            for r, c in batch.prefill_chunks:
                pool.allocate(r.req_id, int(c))
            for r in batch.decode_reqs:
                pool.allocate(r.req_id, 1)
        for r in batch.decode_reqs + [q for q, _ in batch.prefill_chunks]:
            slot = self.slot_of[r.req_id]
            table = pool.tables.get(r.req_id, [])
            n = len(table)
            assert n <= self.max_pages, (
                f"req {r.req_id}: {n} blocks > {self.max_pages} pages"
            )
            seen = int(self._bt_len[slot])
            if slot in self._bt_dirty:
                self._bt_host[slot, :n] = table
                self._bt_host[slot, n:] = self._sink
            elif n > seen:
                self._bt_host[slot, seen:n] = table[seen:]
                self._bt_dirty.add(slot)
            self._bt_len[slot] = n
        if self._bt_dirty:
            rows = sorted(self._bt_dirty)
            # pad the row count to a power-of-2 bucket (repeating one row —
            # duplicate scatter indices carry identical data) so the update
            # only ever compiles the shapes warmup pre-compiled
            k = _pow2_bucket(len(rows))
            rows = np.asarray(rows + [rows[0]] * (k - len(rows)), np.int32)
            self.block_tables = self.block_tables.at[self._put(rows)].set(
                self._put(self._bt_host[rows])
            )
            self._bt_dirty.clear()

    def _split_rows(self, C: int, n: int) -> int:
        """Prefill rows ``P`` of a round of ``n`` chunks at bucket ``C``: the
        power of two that holds them, or 0 where ``(C, P)`` is not a split
        shape and the round runs padded."""
        P = 1 << max(n - 1, 0).bit_length()
        return P if (C, P) in self._split_shapes else 0

    def _stage(self, batch: ScheduledBatch):
        """Host-side staging for one round: token ids (int32 — half the
        host->device width of the seed engine's int64 staging), per-slot
        chunk lengths, and the two masks the fused step needs: which slots
        consume the device-resident ``last_token`` (decodes) and which slots'
        sampled token is meaningful this round (decodes + chunks that finish
        their prefill).

        A round whose widest chunk takes bucket ``C`` runs split where
        ``_split_rows`` gives it ``P > 0`` prefill rows: ``(B, 1)`` decode
        rows plus ``pre = (tokens (P, C), slot of each row)``, rows past the
        chunks pointing at slot ``B`` (masked).  Otherwise every slot is one
        row of ``C`` tokens and ``pre`` is empty."""
        B = self.cfg.n_slots
        chunks = [c for _, c in batch.prefill_chunks]
        C = self._bucket(max(chunks + [1 if batch.decode_reqs else 0]))
        P = self._split_rows(C, len(chunks))
        if self.trace.on:
            n_decode = len(batch.decode_reqs)
            self.trace.count(sum(chunks) + n_decode,
                             B + P * C if P else B * C,
                             len(chunks) + n_decode, C, P)
        tokens = np.zeros((B, 1 if P else C), np.int32)
        chunk_lens = np.zeros((B,), np.int32)
        use_last = np.zeros((B,), np.bool_)
        sample_mask = np.zeros((B,), np.bool_)
        sampled: List[Tuple[Request, int]] = []

        for req in batch.decode_reqs:
            slot = self.slot_of[req.req_id]
            chunk_lens[slot] = 1
            if req.needs_replay:
                # first decode round after a swap-in: the device-resident
                # last_token lane died with the old slot, so stage the last
                # delivered id from the host.  Safe by the drain ordering —
                # every token sampled before the swap-out drained before this
                # round stages (tokens land host-side one round late; the
                # restore itself is one more round later).
                tokens[slot, 0] = req.output_tokens[-1]
                req.needs_replay = False
            else:
                use_last[slot] = True
            sample_mask[slot] = True
            sampled.append((req, slot))
        pre = ()
        if P:
            pre = (np.zeros((P, C), np.int32), np.full((P,), B, np.int32))
        for i, (req, c) in enumerate(batch.prefill_chunks):
            slot = self.slot_of[req.req_id]
            chunk = req.prompt_tokens[req.prefill_done : req.prefill_done + c]
            if P:
                pre[0][i, : len(chunk)] = chunk
                pre[1][i] = slot
            else:
                tokens[slot, : len(chunk)] = chunk
            chunk_lens[slot] = len(chunk)
            if req.remaining_prefill - c <= 0:  # prefill completes this round
                sample_mask[slot] = True
                sampled.append((req, slot))
        return tokens, chunk_lens, use_last, sample_mask, sampled, pre

    def dispatch(self, batch: ScheduledBatch) -> InflightRound:
        """Stage and launch one round WITHOUT waiting for it: the jitted step
        (forward + sampling + length update, one dispatch) runs while the
        caller goes back to scheduling.  The sampled-token readback starts as
        an async device->host copy; ``drain`` collects it one round later."""
        tr = self.trace
        with tr.span("dispatch"):
            with tr.span("stage"):
                tokens, chunk_lens, use_last, sample_mask, sampled, pre = (
                    self._stage(batch))
            args = (self.params, self._put(tokens), self.cache, self.lens,
                    self._put(chunk_lens))
            pre = tuple(map(self._put, pre))
            if self.cfg.paged_kv:
                with tr.span("block_tables"):
                    self._sync_block_tables(batch)
                args += (self.block_tables,)
            args += (self.last_token, self._put(use_last),
                     self._put(sample_mask))
            self._rng, sub = jax.random.split(self._rng)
            t_dispatch = time.perf_counter()
            with tr.span("launch"):
                out = self._step(*args, sub, *pre)
                toks, self.cache, self.lens, self.last_token = out[:4]
                finite = out[4] if len(out) > 4 else None
                toks.copy_to_host_async()
                if finite is not None:
                    finite.copy_to_host_async()
        return InflightRound(toks=toks, sampled=sampled, t_dispatch=t_dispatch,
                             finite=finite)

    def drain(self, inflight: InflightRound) -> float:
        """Block until the round's sampled ids are host-side, then patch the
        REAL token values into the requests' bookkeeping (placeholders were
        recorded by ``on_batch_done`` while the round executed): delivered
        outputs, ``next_token``, and — via ``patch_token`` — any copy a
        preemption already folded into a recompute prompt.  Returns
        dispatch->drain wall ms (device time plus whatever host work it
        overlapped)."""
        with self.trace.span("drain.wait"):
            toks = np.asarray(inflight.toks)
        wall_ms = (time.perf_counter() - inflight.t_dispatch) * 1e3
        with self.trace.span("drain.deliver"):
            if inflight.finite is not None:
                fin = np.asarray(inflight.finite)
                inflight.nonfinite = {
                    req.req_id for req, slot in inflight.sampled
                    if not fin[slot]
                }
            # sync-mode mirror (execute() discards the InflightRound): the
            # serve loop reads the quarantine set of the round it just
            # executed here
            self.last_nonfinite = inflight.nonfinite
            # swap-out staging retires on the same one-round-late path:
            # gathers dispatched before this round's step are host-side by
            # now (or the asarray below bounds the wait)
            self.finalize_swaps()
            for req, slot in inflight.sampled:
                tok = int(toks[slot])
                req.next_token = tok
                idx = inflight.out_index.get(req.req_id)
                if idx is not None:
                    req.patch_token(idx, tok)
        return wall_ms

    def execute(self, batch: ScheduledBatch) -> float:
        """Synchronous round (``pipelined=False`` A/B path): dispatch and
        drain back-to-back, so token ids are delivered before the caller's
        ``on_batch_done`` (with an empty ``out_index`` the drain's patching
        is a no-op and only ``next_token`` delivery remains); returns wall
        latency in ms."""
        return self.drain(self.dispatch(batch))


@dataclass
class ServeResult:
    report: LatencyReport
    requests: List[Request]
    rounds: int
    wall_s: float
    samples: Optional[Tuple[np.ndarray, np.ndarray]] = None
    outputs: Optional[Dict[int, List[int]]] = None
    memory: Optional[MemoryReport] = None     # KV pool lifecycle summary
    # per round, drain.wait end -> launch start (trace.host_bubbles_ms)
    host_bubble_ms: Optional[List[float]] = None
    slo: Optional[SLOReport] = None           # per-tenant attainment gauges
    robustness: Optional["RobustnessReport"] = None  # chaos/fault summary


def compress_idle_gap(pending: List[Request], next_i: int, now: float) -> None:
    """Jump the idle gap to the next arrival by shifting ALL future arrivals
    by the same constant, so inter-arrival gaps — and therefore arrival-order
    and aging behavior — are preserved mid-run."""
    offset = now - pending[next_i].arrival_time
    for j in range(next_i, len(pending)):
        pending[j].arrival_time += offset


class ReplicaServer:
    """One replica's continuous-batching state machine: the body of
    ``serve()`` factored into admit/step/drain pieces so a multi-replica
    driver (``repro.disagg.DisaggregatedRouter``) can interleave several
    engines — each with its own scheduler and pool — inside one host loop,
    while single-replica ``serve()`` stays a thin wrapper.

    ``step(now)`` runs at most one scheduling round and reports what
    happened:
      * ``"round"``     — a batch was dispatched (pipelined) or executed
      * ``"drained"``   — progress was made by draining the in-flight round
      * ``"finalized"`` — pending swap-out copies were landed (no round ran)
      * ``"starved"``   — runnable work exists but nothing could be placed
      * ``"idle"``      — no queued or in-flight work at all

    Value-dependent stop tokens (``Request.stop_token``) are honored here,
    not in ``receive_token``: a pipelined engine learns token VALUES one
    round late, so the stop is applied at drain time — by which point the
    request may already be booked into the next, not-yet-dispatched round
    (unwound via ``scheduler.on_stop``, which also refunds the
    over-scheduled round's KV booking), preempted, or mid-handoff.  Greedy
    outputs stay bit-identical to the synchronous engine, which observes the
    same stop in the same round's ``on_batch_done``.
    """

    def __init__(
        self,
        scheduler: ChunkedPrefillScheduler,
        engine: JAXEngine,
        *,
        kv_pool: Optional[KVBlockPool] = None,
        collect_samples: bool = False,
        on_prefill_complete=None,
        on_stopped=None,
        name: str = "replica",
    ):
        self.sched = scheduler
        self.engine = engine
        self.kv_pool = kv_pool
        self.collect_samples = collect_samples
        # multi-replica hook: called once per request in the round its
        # prefill completed (state DECODING, first token bookkept) — the
        # disaggregated router decides there whether to export the KV
        self.on_prefill_complete = on_prefill_complete
        # multi-replica hook: called after a value-dependent stop is applied
        # (scheduler.on_stop already ran) — the router chases a prefetched
        # handoff record to whatever pool it moved on to and unwinds it there
        self.on_stopped = on_stopped
        self.name = name
        self.pipelined = engine.cfg.pipelined
        self.inflight: Optional[InflightRound] = None
        self.rounds = 0
        self.outputs: Dict[int, List[int]] = {}
        # fault tolerance (repro.robustness): an attached injector fires
        # seeded chaos sites inside step(); fault_tolerant converts any
        # exception out of a round into a crash unwind + "error" status
        # instead of tearing down the serve loop
        self.injector = None
        self.fault_tolerant = False
        self.last_error: Optional[BaseException] = None
        self.crash_unwinds = 0
        self.crash_requeued = 0
        # local retry bound: a request requeued by _crash_cleanup more than
        # max_crash_retries times sheds terminally instead of cycling — on a
        # single replica there is no fleet to fail over to, and a repeating
        # crash site must not livelock the serve loop (None = unbounded)
        self.max_crash_retries: Optional[int] = None
        self._crash_retries: Dict[int, int] = {}
        self.crash_shed: List[Request] = []
        self.quarantined: List[Request] = []
        # torn-round bookkeeping for _crash_cleanup: the round being drained
        # (popped off self.inflight but not yet patched/delivered) and the
        # batch scheduled-but-not-yet-retired by on_batch_done
        self._draining: Optional[InflightRound] = None
        self._pending_batch: Optional[ScheduledBatch] = None
        self.feats: List[np.ndarray] = []
        self.lats: List[float] = []
        self.t_start = time.perf_counter()

        if kv_pool is not None:
            if scheduler.kv_pool is None:
                # the scheduler books blocks chunk-granularly inside schedule()
                scheduler.attach_kv_pool(kv_pool)
            engine.bind_kv_pool(kv_pool)
        # slots bind at first schedule and free at preemption, not admission
        scheduler.attach_slot_binder(engine.acquire_slot, releaser=engine.release)
        if scheduler.kv_pool is not None and scheduler.kv_booking:
            # preemption mode comes from the ENGINE config (it owns the
            # physical swap path); the deterministic cost model prices swap
            # bytes vs recompute FLOPs per victim
            scheduler.attach_swap(
                engine.swap_out, engine.swap_in,
                cost_model=CostModel(CostModelConfig(noise_std=0.0)),
                mode=engine.cfg.preemption_mode,
                restorer_tail=engine.swap_in_tail,
                payload_slicer=engine.slice_swap_payload,
            )
        # this replica's spans and counters, recorded by its engine too
        self.trace = Recorder()
        engine.trace = self.trace

    # -- clock ----------------------------------------------------------------
    def start(self, t_start: float) -> None:
        """Anchor this replica's clock (a multi-replica driver shares one)."""
        self.t_start = t_start

    def _now(self) -> float:
        return time.perf_counter() - self.t_start

    # -- intake ---------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Admit one request: pool registration (tenant + prompt hashes
        only — the prefix-cache MATCH waits for first slot bind, so a parked
        backlog pins no cached blocks and no tenant quota) plus scheduler
        submission."""
        if self.kv_pool is not None:
            self.kv_pool.register_request(
                req.req_id, tenant=req.tenant,
                prompt_tokens=req.prompt_tokens, prompt_len=req.prompt_len,
            )
        if not self.sched.submit(req):         # admission-rejected: give back
            if self.kv_pool is not None:
                self.kv_pool.release(req.req_id)

    def adopt_handoff(self, req: Request, rec, reg) -> None:
        """Decode-pool side of a cross-replica handoff: land the exported
        staging record in this replica's pool and enqueue the request.  The
        ordinary swap-restore path inside ``schedule()`` then binds a slot,
        re-charges the tenant's quota, scatters the payload, and resumes the
        request decode-only (``needs_replay`` stages its last delivered
        token) — no prefill chunk is ever scheduled for it here."""
        self.kv_pool.import_swap(req.req_id, rec, reg)
        self.sched.submit_handoff(req)

    # -- introspection ---------------------------------------------------------
    def has_work(self) -> bool:
        return self.sched.has_work()

    def has_inflight(self) -> bool:
        return self.inflight is not None

    def busy(self) -> bool:
        return (self.sched.has_work() or self.inflight is not None
                or self.engine.has_pending_swaps())

    def outstanding_work(self) -> int:
        """Tokens of runnable work currently on this replica (prefill left +
        decode left over queued/decoding requests) — the router's load key."""
        total = 0
        for r in self.sched.queue.requests():
            total += r.remaining_prefill + (r.max_new_tokens - r.generated)
        for r in self.sched._decoding.values():
            total += r.remaining_prefill + (r.max_new_tokens - r.generated)
        return total

    def tenant_outstanding(self, tenant: str) -> int:
        total = 0
        for r in list(self.sched.queue.requests()) + list(
                self.sched._decoding.values()):
            if r.tenant == tenant:
                total += r.remaining_prefill + (r.max_new_tokens - r.generated)
        return total

    # -- one scheduling round --------------------------------------------------
    def step(self, now: float) -> str:
        """Run one round, optionally under the fault boundary: chaos sites
        fire here and — when ``fault_tolerant`` — any exception out of the
        round (injected or real) is converted into a crash unwind plus an
        ``"error"`` status the health machinery consumes, instead of tearing
        down the whole serve loop."""
        if self.injector is None and not self.fault_tolerant:
            return self._step_impl(now)
        try:
            inj = self.injector
            if inj is not None:
                spec = inj.fire("slow_round_ms", replica=self.name)
                if spec is not None:
                    time.sleep(max(spec.value, 0.0) / 1e3)
                inj.maybe_raise("replica_step_crash", replica=self.name)
            return self._step_impl(now)
        except Exception as e:  # noqa: BLE001 — the replica fault boundary
            if not self.fault_tolerant:
                raise
            self.last_error = e
            self._crash_cleanup()
            return "error"

    def _step_impl(self, now: float) -> str:
        """One step; with recording on, a step that runs a round is the
        ``round`` span (the profiler's ``round`` annotation covers every
        step)."""
        tr = self.trace
        tr.round_id = self.rounds
        if not tr.on:
            return self._step_body(now)
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("round"):
            status = self._step_body(now)
        if status == "round":
            tr.add("round", t0, time.perf_counter_ns())
        return status

    def _step_body(self, now: float) -> str:
        sched, engine, tr = self.sched, self.engine, self.trace
        drained_eagerly = False
        if self.inflight is not None and self.inflight.toks.is_ready():
            # device already finished: drain before (not after) the next
            # schedule — tokens/timestamps stamp at true readiness and the
            # bubble metric doesn't hide idle time behind the overlap
            self._drain_inflight()
            drained_eagerly = True
        if not sched.has_work():
            if self.inflight is not None:
                self._drain_inflight()
                return "drained"
            if engine.has_pending_swaps():
                # an exported (handoff) request's gather can be the only
                # pending work on this replica — land it so the router can
                # move the staged record on
                with tr.span("finalize_swaps"):
                    engine.finalize_swaps()
                return "finalized"
            # an eager drain above counts as progress — it may have just
            # finalized an exported gather the router is waiting on, so
            # "idle" (a quiesce signal) would be premature this step
            return "drained" if drained_eagerly else "idle"

        # preemption victims' slots were already freed inside schedule() (the
        # releaser hook) — a victim may even have re-bound a fresh slot and
        # been rescheduled within the same round, so do NOT release here.
        # In pipelined mode this schedule overlaps the in-flight round.
        with tr.span("schedule"):
            batch = sched.schedule(now)
        if tr.on:
            # a request's first scheduled chunk ends its wait in the queue
            t_sched = time.perf_counter_ns()
            for r, _c in batch.prefill_chunks:
                if not r.chunks:
                    tr.add("queued", int((self.t_start + r.arrival_time) * 1e9),
                           t_sched, r.req_id)
        if batch.is_empty():
            if self.inflight is not None:
                self._drain_inflight()
                return "drained"
            if engine.has_pending_swaps():
                # nothing in flight to piggyback the staging drain on (e.g.
                # every runnable request is a SWAPPING victim): finalize now
                # so the next schedule() round can restore them
                with tr.span("finalize_swaps"):
                    engine.finalize_swaps()
                return "finalized"
            return "drained" if drained_eagerly else "starved"

        # the batch is booked and counted but not yet retired: a crash
        # anywhere before on_batch_done must strip it back out of the stats
        self._pending_batch = batch
        if self.injector is not None:
            for r in batch.decode_reqs:
                if self.injector.fire("nan_logits", replica=self.name,
                                      req_id=r.req_id) is not None:
                    engine.poison_kv(r)

        if self.pipelined:
            if self.inflight is not None:
                # round N-1's ids land BEFORE round N+1 stages anything that
                # could embed them (a preemption fold re-prefills delivered
                # tokens) — this is the pipeline's one-round visibility lag.
                # The just-scheduled batch rides along so a late stop can be
                # unwound from it before it dispatches.
                self._drain_inflight(pending_batch=batch)
            self.inflight = engine.dispatch(batch)
            self.inflight.batch = batch
            wall_ms = None
        else:
            wall_ms = engine.execute(batch)
        if self.kv_pool is not None:
            # newly sealed (full, hashed) prompt blocks become restorable
            with tr.span("capture_sealed"):
                for r, _c in batch.prefill_chunks:
                    engine.capture_sealed(r)
        if self.collect_samples:
            self.feats.append(batch.state.features())
            if wall_ms is not None:
                self.lats.append(wall_ms)
        self.rounds += 1

        now2 = self._now()
        with tr.span("on_batch_done"):
            sched.on_batch_done(batch, now2)   # releases finished KV refs
        self._pending_batch = None             # retired: charged and counted

        # sync-mode numerics quarantine: execute() drained inside the round,
        # so the finite mask is already host-visible.  Roll back the poisoned
        # token (its charge refunds), shed terminally, deliver the clean
        # prefix.  Pipelined mode does the same one round late, at drain.
        if not self.pipelined and engine.last_nonfinite:
            prefill_ids = {q.req_id for q, _ in batch.prefill_chunks}
            for r in batch.decode_reqs + [q for q, _ in batch.prefill_chunks]:
                if r.req_id not in engine.last_nonfinite:
                    continue
                if r.rollback_undrained(1):
                    sched.refund_rolled_back(
                        r, first_token=r.req_id in prefill_ids)
                sched.shed_request(r, reason="numerics")
                self.outputs[r.req_id] = list(r.output_tokens)
                self.quarantined.append(r)
                if self.on_stopped is not None:
                    self.on_stopped(self, r)
            engine.scrub_poisoned()

        if self.pipelined:
            # the placeholder each sampled request just received sits at the
            # tail of its output_tokens; drain() patches the real id there
            for req, _slot in self.inflight.sampled:
                self.inflight.out_index[req.req_id] = len(req.output_tokens) - 1
            # sampled ∩ prefill = chunks that completed their prefill this
            # round: their prefill_end_time re-stamps at drain
            self.inflight.prefill_ids = {r.req_id for r, _ in batch.prefill_chunks}

        for r in batch.decode_reqs + [q for q, _ in batch.prefill_chunks]:
            self.outputs.setdefault(r.req_id, [])
            if r.state == RequestState.FINISHED:
                if self.pipelined:
                    self.inflight.finished.append(r)
                else:
                    self.outputs[r.req_id] = list(r.output_tokens)
                engine.release(r)

        if not self.pipelined:
            # synchronous engine: token values are already real (execute()
            # drains internally), so stops and per-token timestamps apply in
            # the same round
            for r in batch.decode_reqs + [q for q, _ in batch.prefill_chunks]:
                if r.req_id in engine.last_nonfinite:
                    continue       # quarantined above: its token rolled back
                if r.remaining_prefill == 0 and r.output_tokens:
                    r.token_times.append(now2)
                if (r.stop_token is not None
                        and r.state == RequestState.DECODING
                        and r.output_tokens
                        and r.output_tokens[-1] == r.stop_token):
                    r.finish_stopped(now2)
                    self.outputs[r.req_id] = list(r.output_tokens)
                    sched.on_stop(r)
                    if self.on_stopped is not None:
                        self.on_stopped(self, r)

        if self.on_prefill_complete is not None:
            for r, _c in batch.prefill_chunks:
                if r.state == RequestState.DECODING and r.remaining_prefill == 0:
                    self.on_prefill_complete(self, r)
        return "round"

    # -- drain -----------------------------------------------------------------
    def _drain_inflight(self, pending_batch: Optional[ScheduledBatch] = None) -> None:
        inflight, self.inflight = self.inflight, None
        # visible to _crash_cleanup until this round is fully delivered: a
        # crash inside drain/stop processing must unwind it, not strand it
        self._draining = inflight
        wall_ms = self.engine.drain(inflight)
        if self.collect_samples:
            self.lats.append(wall_ms)
        with self.trace.span("drain.deliver"):
            self._deliver(inflight, pending_batch)
        self._draining = None

    def _deliver(self, inflight: InflightRound,
                 pending_batch: Optional[ScheduledBatch]) -> None:
        """Stamp, deliver and stop the drained round's requests."""
        # timestamps recorded against the placeholder `now` are re-stamped to
        # the moment the ids actually became host-visible — the earliest a
        # client could receive them — so pipelined LatencyReports are not
        # systematically understated vs the synchronous engine's
        now_v = self._now()
        # numerics quarantine FIRST: a request whose sampled logits were
        # non-finite must not stamp, deliver, or stop on the garbage id.  The
        # poisoned placeholder rolls back (charge refunded), the request
        # sheds terminally, and its clean delivered prefix is the output.
        for req, _slot in inflight.sampled:
            if req.req_id not in inflight.nonfinite:
                continue
            if req in inflight.finished:
                inflight.finished.remove(req)
            if req.rollback_undrained(1):
                self.sched.refund_rolled_back(
                    req, first_token=req.req_id in inflight.prefill_ids)
            self.sched.shed_request(
                req, reason="numerics", batch=pending_batch)
            self.outputs[req.req_id] = list(req.output_tokens)
            self.quarantined.append(req)
            if self.on_stopped is not None:
                self.on_stopped(self, req)
        if inflight.nonfinite:
            self.engine.scrub_poisoned()
        for req, _slot in inflight.sampled:
            if req.req_id in inflight.nonfinite:
                continue
            if inflight.out_index.get(req.req_id) == 0:
                req.first_token_time = now_v
            if req.req_id in inflight.prefill_ids:
                req.prefill_end_time = now_v
            req.token_times.append(now_v)
        for r in inflight.finished:
            r.finish_time = now_v
            # patched ids are final only now — deliver them
            self.outputs[r.req_id] = list(r.output_tokens)
        # value-dependent stops, one round late: only now are the sampled ids
        # real.  A stopping request may meanwhile have been booked into the
        # next round (pending_batch — scheduled but not yet dispatched),
        # preempted to the queue, swap-staged, or exported for a handoff;
        # on_stop unwinds each of those (the over-scheduled round's KV
        # booking is refunded with the release).
        for req, _slot in inflight.sampled:
            if req.stop_token is None or req.state == RequestState.FINISHED:
                continue
            idx = inflight.out_index.get(req.req_id)
            if idx is None or req.output_tokens[idx] != req.stop_token:
                continue
            req.finish_stopped(now_v)
            self.outputs[req.req_id] = list(req.output_tokens)
            self.sched.on_stop(req, pending_batch)
            if self.on_stopped is not None:
                self.on_stopped(self, req)

    # -- crash unwind ----------------------------------------------------------
    def _crash_cleanup(self) -> None:
        """A step crashed somewhere between scheduling and delivery: unwind
        the torn round(s) so this replica (or, after failover, its
        survivors) can carry on without leaking slots, KV blocks, or phantom
        VTC charges.

        Up to three torn artifacts can exist:
          * ``_draining``      — a round popped by ``_drain_inflight`` that
                                 crashed before its tokens were delivered,
          * ``self.inflight``  — a round dispatched but never drained,
          * ``_pending_batch`` — a batch scheduled (KV booked, stats counted)
                                 whose ``on_batch_done`` never ran.

        Undrained placeholder tokens roll back and their charge refunds (the
        values never became host-visible; greedy recompute regenerates them
        bit-identically).  Every involved live request is then evicted from
        the scheduler, folded via ``preempt()`` (at-most-once delivery), and
        re-queued locally.  Already-delivered requests are left alone."""
        torn: List[InflightRound] = []
        if self._draining is not None:
            torn.append(self._draining)
            self._draining = None
        if self.inflight is not None:
            torn.append(self.inflight)
            self.inflight = None
        pending = self._pending_batch
        self._pending_batch = None

        victims: Dict[int, Request] = {}
        for infl in torn:
            for req, _slot in infl.sampled:
                victims[req.req_id] = req
            for req in infl.finished:
                victims[req.req_id] = req
            if infl.batch is not None:
                for req in infl.batch.decode_reqs:
                    victims[req.req_id] = req
                for req, _c in infl.batch.prefill_chunks:
                    victims[req.req_id] = req
        if pending is not None:
            for req in pending.decode_reqs:
                victims[req.req_id] = req
            for req, _c in pending.prefill_chunks:
                victims[req.req_id] = req

        for infl in torn:
            for req, _slot in infl.sampled:
                if infl.out_index.get(req.req_id) is None:
                    continue   # crash hit before the placeholder bookkeeping
                if (req.state == RequestState.FINISHED
                        and self.outputs.get(req.req_id)):
                    continue   # fully delivered before the crash: irrevocable
                if req.rollback_undrained(1):
                    self.sched.refund_rolled_back(
                        req, first_token=req.req_id in infl.prefill_ids)

        for req in victims.values():
            if req.state == RequestState.FINISHED:
                continue       # delivered, stopped, or shed before the crash
            if (self.kv_pool is not None
                    and req.req_id not in self.kv_pool._reg
                    and self.kv_pool.swap_state(req.req_id) is None
                    and not self.kv_pool.tables.get(req.req_id)):
                # no longer owned here: the round that tore also completed
                # this request's prefill and the router exported its handoff
                # (export_swap popped the registration) before the crash.
                # Its placeholder rolled back above; the handoff pipeline (or
                # the router's failover retraction, if this replica is dying)
                # owns its fate now.
                continue
            k = self._crash_retries.get(req.req_id, 0) + 1
            self._crash_retries[req.req_id] = k
            if (self.max_crash_retries is not None
                    and k > self.max_crash_retries):
                self.sched.shed_request(
                    req, reason="replica_failure", batch=pending)
                self.outputs[req.req_id] = list(req.output_tokens)
                self.crash_shed.append(req)
                continue
            self.sched.evict_request(req, pending)
            req.preempt()
            if self.kv_pool is not None:
                self.kv_pool.register_request(
                    req.req_id, tenant=req.tenant,
                    prompt_tokens=req.prompt_tokens,
                    prompt_len=req.prompt_len,
                )
            self.sched.requeue_failed(req)
            self.crash_requeued += 1
        self.crash_unwinds += 1

    def finish(self) -> None:
        """End-of-serve cleanup: drain the last round and land any pending
        swap copies (no staging entry is left mid-flight at exit)."""
        if self.inflight is not None:
            self._drain_inflight()
        with self.trace.span("finalize_swaps"):
            self.engine.finalize_swaps()


def serve(
    requests: List[Request],
    scheduler: ChunkedPrefillScheduler,
    engine: JAXEngine,
    *,
    kv_pool: Optional[KVBlockPool] = None,
    collect_samples: bool = False,
    realtime_arrivals: bool = False,
    max_rounds: int = 200_000,
    robustness=None,
) -> ServeResult:
    """Continuous-batching serve loop over real execution.

    Admission hands requests straight to the scheduler — an engine slot is
    bound only when the scheduler first commits a chunk (late binding, via
    the slot-binder hook), so queued or admission-delayed backlog can never
    pin slots.

    With ``EngineConfig(pipelined=True)`` (default) the loop runs as a
    two-stage pipeline: while the device executes round N, the host runs
    admission + ``schedule()`` (aging, VTC, KV booking, preemption) for
    round N+1 and drains round N's sampled ids as an async copy — round N's
    token VALUES become host-visible one round late, which is fine because
    round bookkeeping (chunk deliveries, length-capped termination) is
    value-independent and the values themselves are only needed for
    delivered outputs, stop-token termination, and preemption folds, all
    patched/applied at drain time before anything is staged from them.
    ``collect_samples`` latencies in pipelined mode are dispatch->drain
    walls (device time plus overlapped host work).

    The loop body lives in ``ReplicaServer`` (one replica's admit/step/drain
    state machine); this wrapper owns only arrival admission and idle-gap
    handling.  realtime_arrivals=False (default) admits requests by the
    engine's own clock (wall time since start), compressing idle gaps —
    deterministic and fast for tests; True sleeps to honor arrival times.
    """
    pending = sorted(requests, key=lambda r: r.arrival_time)
    for r in pending:
        assert r.prompt_tokens is not None, "attach_prompt_tokens() first"
    server = ReplicaServer(
        scheduler, engine, kv_pool=kv_pool, collect_samples=collect_samples,
    )
    server.trace.on = True          # host_bubble_ms reads the spans
    if robustness is not None:
        # colocated fault tolerance: crash unwinds + NaN quarantine survive
        # in-place (there is no second replica to fail over to — replica
        # death/failover lives in the disaggregated router)
        server.fault_tolerant = True
        server.injector = robustness.make_injector()
        server.max_crash_retries = robustness.max_retries
    # the same health machine the fleet router runs, over the lone replica:
    # a persistent fault (a repeat-crash site, a wedged device) must not
    # spin the serve loop forever — once DEAD, remaining work sheds
    # terminally (exactly-once termination with no fleet to fail over to)
    health = (ReplicaHealth(robustness.health, "replica0")
              if robustness is not None else None)
    next_i = 0
    t_start = time.perf_counter()
    server.start(t_start)
    now = 0.0

    while server.rounds < max_rounds:
        now = time.perf_counter() - t_start
        while next_i < len(pending) and pending[next_i].arrival_time <= now:
            server.submit(pending[next_i])
            next_i += 1
        status = server.step(now)
        if health is not None:
            health.observe(status, busy=server.busy(),
                           error=server.last_error
                           if status == "error" else None)
            if health.is_dead:
                break
        if status == "idle":
            if next_i >= len(pending):
                break
            if realtime_arrivals:
                time.sleep(min(0.001, pending[next_i].arrival_time - now))
            else:
                compress_idle_gap(pending, next_i, now)
        elif status == "starved":
            time.sleep(0.0005)

    if health is not None and health.is_dead:
        # the lone replica died: every request not already terminal sheds.
        # Submitted requests unwind their bookings through the scheduler;
        # unarrived backlog never registered anything and just marks shed.
        for i, r in enumerate(pending):
            if r.state == RequestState.FINISHED:
                continue
            if i < next_i:
                scheduler.shed_request(r, reason="replica_failure")
            else:
                r.shed_reason = "replica_failure"
                r.state = RequestState.FINISHED
            server.outputs[r.req_id] = list(r.output_tokens)
            server.crash_shed.append(r)

    server.finish()
    now = time.perf_counter() - t_start

    samples = (
        (np.stack(server.feats), np.asarray(server.lats))
        if collect_samples and server.feats else None
    )
    return ServeResult(
        report=summarize(requests, makespan=now),
        requests=requests,
        rounds=server.rounds,
        wall_s=now,
        samples=samples,
        outputs=server.outputs,
        memory=(
            summarize_memory(kv_pool, scheduler.stats) if kv_pool is not None else None
        ),
        host_bubble_ms=host_bubbles_ms(server.trace.spans),
        slo=(
            summarize_slo(requests, scheduler.fairness.registry)
            if scheduler.fairness is not None else None
        ),
        robustness=(
            summarize_robustness(
                FailoverStats(), injector=server.injector,
                quarantined=len(server.quarantined),
                crash_unwinds=server.crash_unwinds,
                crash_shed=len(server.crash_shed),
            )
            if robustness is not None else None
        ),
    )
