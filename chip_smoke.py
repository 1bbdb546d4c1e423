#!/usr/bin/env python3
"""Smoke run of the paged serving path on TPU chips.

    python3 chip_smoke.py            # one chip: phases oracle, pallas, swap
    python3 chip_smoke.py --chips 4  # four chips: the replica fleet only

Drives the entry points a user calls — ``JAXEngine``, ``serve()``,
``ChunkedPrefillScheduler`` and ``KVBlockPool`` (``build_disagg`` and
``serve_disagg`` for the fleet) — at the published widths and full depth of
qwen1.5-0.5b, with random weights from a fixed seed and seeded ShareGPT-like
prompts, so nothing is downloaded.  Everything runs in this one process.

* oracle — the jnp gather attention (the engine's default path).
* pallas — the Mosaic-compiled paged kernels.  The compiled step must hold
  ``tpu_custom_call``; on fixed mixed batches (prefill and decode rows) the
  attention kernels and the step's logits must agree with the oracle's
  within ``ATTN_TOL`` and ``LOGIT_TOL``.
* swap — the kernels with swap preemption on a pool too small for the
  batch, which puts the swap gather and scatter kernels on the chip.  Then
  random pages gathered to the host and scattered back must return bit for
  bit.
* fleet (``--chips 4``) — one prefill and three decode replicas, one per
  chip, against a single engine on chip 0.  Random pages moved from the
  prefill replica's pool, through the host, into each decode replica's pool
  must arrive bit for bit.

Every request must finish with its full token count and finite logits.  A
failed check raises, so the exit code is non-zero; there is no CPU
fallback.  The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.request import RequestState  # noqa: E402
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig  # noqa: E402
from repro.disagg import DisaggConfig, build_disagg, serve_disagg  # noqa: E402
from repro.engine.engine import EngineConfig, JAXEngine, serve  # noqa: E402
from repro.engine.kv_cache import pool_for_model  # noqa: E402
from repro.engine.workload import (  # noqa: E402
    WorkloadSpec, attach_prompt_tokens, sharegpt_like,
)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402

MODEL = "qwen1.5-0.5b"
SEED = 2                  # its 8 prompts span 1..256 tokens: every chunk bucket
N_REQUESTS = 8
MAX_PROMPT = 256
NEW_TOKENS = 16
N_SLOTS = 16
MAX_CONTEXT = 512
TOKEN_BUDGET = 256
POOL_BLOCKS = 2048
SWAP_POOL_BLOCKS = 24     # the batch needs 44 blocks at once: victims swap
# Kernel and oracle both do f32 attention math over a bf16 pool, but in
# different accumulation orders (and a TPU's default f32 dot rounds its
# inputs to bf16), so outputs differ by a few bf16 steps.  ATTN_TOL bounds
# |kernel - oracle| / (1 + |oracle|) per element, as the interpret-mode
# parity tests do.  LOGIT_TOL bounds max |kernel - oracle| over the step's
# logits relative to max |logit|: on a CPU at 2 and 6 layers, rounding-
# level noise on every V element moved logits by 1.6% and 2.7% of max
# |logit| while a wrong K/V gather moved them by 140-150%.
ATTN_TOL = 2e-2
LOGIT_TOL = 0.25


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu(n_chips: int) -> jax.Device:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is a "
            f"{devices[0].platform!r} device; no phase ran")
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: --chips {n_chips} needs {n_chips} TPU devices, "
            f"JAX found {len(devices)}; no phase ran")
    return devices[0]


def model_config():
    return get_config(MODEL)


def make_requests(vocab_size: int):
    reqs = sharegpt_like(WorkloadSpec(
        n_requests=N_REQUESTS, inter_arrival_s=0.005, max_context=MAX_PROMPT,
        max_new_tokens=NEW_TOKENS, seed=SEED,
    ))
    attach_prompt_tokens(reqs, vocab_size, seed=SEED)
    return reqs


def sched_config() -> SchedulerConfig:
    return SchedulerConfig(policy="aging", token_budget=TOKEN_BUDGET,
                           max_seqs=N_SLOTS)


def engine_config(**kw) -> EngineConfig:
    # nan_guard sheds any request whose sampled logits go non-finite, which
    # check_finished then reports
    return EngineConfig(n_slots=N_SLOTS, max_context=MAX_CONTEXT,
                        nan_guard=True, **kw)


def check_finished(phase: str, reqs, outputs) -> None:
    short = [r.req_id for r in reqs
             if r.state != RequestState.FINISHED or r.shed_reason is not None
             or len(outputs.get(r.req_id, ())) != r.max_new_tokens]
    check(not short, f"{phase}: requests {short} did not finish with their "
                     f"full token count and finite logits")


def output_pairs(reqs_a, out_a, reqs_b, out_b):
    """Request id (of run a) -> its outputs in runs a and b of the same
    request list."""
    return {a.req_id: (out_a[a.req_id], out_b[b.req_id])
            for a, b in zip(reqs_a, reqs_b)}


def identical_share(pairs):
    """(share of requests, share of tokens) identical across output pairs."""
    same_req = sum(x == y for x, y in pairs) / len(pairs)
    same_tok = (sum(sum(s == t for s, t in zip(x, y)) for x, y in pairs)
                / sum(len(x) for x, _ in pairs))
    return same_req, same_tok


def serve_phase(phase: str, kind: str, engine_cfg: EngineConfig, n_blocks: int,
                params=None):
    """Build, warm and serve the seeded requests on one engine on chip 0."""
    model_cfg = model_config()
    engine = JAXEngine(model_cfg, engine_cfg, params=params)
    pool = pool_for_model(model_cfg, n_blocks=n_blocks)
    engine.bind_kv_pool(pool)
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    reqs = make_requests(model_cfg.vocab_size)
    t0 = time.perf_counter()
    res = serve(reqs, ChunkedPrefillScheduler(sched_config()), engine,
                kv_pool=pool)
    serve_s = time.perf_counter() - t0
    check_finished(phase, reqs, res.outputs)
    n_tok = sum(len(v) for v in res.outputs.values())
    print(f"[{phase}] {kind} x1: compile (warmup) {compile_s:.1f} s, serve "
          f"{serve_s:.2f} s, {res.rounds} rounds, {len(reqs)} requests, "
          f"{n_tok} tokens")
    return engine, reqs, res


def paged_inputs(model_cfg, C: int, rng):
    """One fixed round of ``N_SLOTS`` rows: with ``C > 1`` half the rows
    prefill a full chunk after a prefix (some empty) and half decode one
    token; with ``C == 1`` every row decodes.  K/V pages are N(0,1) and the
    block tables a permutation of the pool."""
    B, ps = N_SLOTS, 16
    max_pages = MAX_CONTEXT // ps + 1
    n_phys = B * max_pages + 1
    lanes = model_cfg.n_kv_heads * model_cfg.resolved_head_dim
    shape = (model_cfg.n_layers, n_phys, ps, lanes)
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
            for _ in range(2))
    bt = rng.permutation(n_phys - 1)[: B * max_pages].reshape(B, max_pages)
    prefill = np.arange(B) >= (B // 2 if C > 1 else B)
    chunk = np.where(prefill, C, 1).astype(np.int32)
    lens = rng.integers(0, MAX_CONTEXT - C, B).astype(np.int32)
    lens[prefill & (np.arange(B) % 4 == 0)] = 0
    tokens = rng.integers(1, model_cfg.vocab_size, (B, C)).astype(np.int32)
    return (jnp.asarray(tokens), {"k": k, "v": v}, jnp.asarray(lens),
            jnp.asarray(chunk), jnp.asarray(bt, jnp.int32))


def attention_parity(model_cfg, kind: str, rng) -> None:
    """Paged decode and prefill kernels, split and fused layouts, against
    the gather oracles at the model's head shapes."""
    _, cache, lens, chunk, bt = paged_inputs(model_cfg, 16, rng)
    k, v = cache["k"][0], cache["v"][0]
    hd = model_cfg.resolved_head_dim
    kv = ref.fuse_pages(*(p.reshape(p.shape[:2] + (-1, hd)) for p in (k, v)))
    kv = kv.reshape(kv.shape[:2] + (-1,))
    Hq = model_cfg.n_heads
    q1 = jnp.asarray(rng.standard_normal((N_SLOTS, Hq, hd)), jnp.bfloat16)
    qc = jnp.asarray(rng.standard_normal((N_SLOTS, 16, Hq, hd)), jnp.bfloat16)
    kv_lens = lens + 16
    cases = {
        "decode/split": lambda p: ops.paged_flash_decode_attention(
            q1, k, v, bt, lens + 1, use_pallas=p),
        "decode/fused": lambda p: ops.paged_flash_decode_attention_fused(
            q1, kv, bt, lens + 1, use_pallas=p),
        "prefill/split": lambda p: ops.paged_prefill_chunk_attention(
            qc, k, v, bt, kv_lens, lens, use_pallas=p),
        "prefill/fused": lambda p: ops.paged_prefill_chunk_attention_fused(
            qc, kv, bt, kv_lens, lens, use_pallas=p),
    }
    for name, f in cases.items():
        got, want = (np.asarray(f(p), np.float32) for p in (True, False))
        err = float((np.abs(got - want) / (1 + np.abs(want))).max())
        print(f"[pallas] {kind}: attention {name} max|kernel - oracle|/"
              f"(1+|oracle|) = {err:.3e} (tolerance {ATTN_TOL})")
        check(np.isfinite(got).all() and err <= ATTN_TOL,
              f"attention {name} off the oracle by {err}")


def logit_parity(engine, kind: str, rng) -> None:
    """The model step on fixed rounds (C=16 mixed prefill/decode, C=1 all
    decode) with the kernels against the same step with the oracles."""
    impl, params = engine.model.impl, engine.params
    for C in (16, 1):
        args = paged_inputs(engine.model_cfg, C, rng)
        logits = {}
        for use_pallas in (True, False):
            step = jax.jit(functools.partial(impl.chunked_step_paged,
                                             use_pallas=use_pallas))
            logits[use_pallas] = np.asarray(step(params, *args)[0], np.float32)
        got, want = logits[True], logits[False]
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max())
        print(f"[pallas] {kind}: step logits C={C} max|kernel - oracle| = "
              f"{err:.3e}, max|logit| = {scale:.3e} (tolerance "
              f"{LOGIT_TOL} x max|logit|)")
        check(np.isfinite(got).all() and err <= LOGIT_TOL * scale,
              f"step logits at C={C} off the oracle by {err}")


def page_round_trip(phase: str, src: JAXEngine, dst: JAXEngine, rng,
                    n_pages: int = 16) -> None:
    """Swap and handoff move pages, so they must move them bit for bit.
    Random pages are scattered into ``src``'s pool, gathered there, copied
    to the host, scattered into ``dst``'s pool (the swap-in and handoff
    path: onto ``dst``'s device, then the scatter kernel) and gathered back.
    Both pools are idle once serving is done, so any pages will do."""
    for nm in src._cache_names():
        shape = src.cache[nm].shape
        want = np.asarray(jnp.asarray(rng.standard_normal(
            (shape[0], n_pages) + shape[2:]), jnp.bfloat16))
        ids_src, ids_dst = (
            rng.permutation(e.kv_pool.cfg.n_blocks)[:n_pages].astype(np.int32)
            for e in (src, dst))
        src._scatter_staged(nm, src._put(ids_src), want)
        staged = np.asarray(ops.gather_swap_pages(
            src.cache[nm], src._put(ids_src), use_pallas=src.cfg.use_pallas))
        dst._scatter_staged(nm, dst._put(ids_dst), staged)
        back = dst.cache[nm]
        check(back.devices() == {dst.device},
              f"{phase}: {nm} pool left device {dst.device}")
        back = np.asarray(ops.gather_swap_pages(
            back, dst._put(ids_dst), use_pallas=dst.cfg.use_pallas))
        same = [np.array_equal(a.view(np.uint16), want.view(np.uint16))
                for a in (staged, back)]
        print(f"[{phase}] {nm}: {n_pages} pages x {shape[0]} layers, device "
              f"{src.device.id} -> host -> device {dst.device.id}: "
              f"bit-identical after gather {same[0]}, after scatter and "
              f"gather {same[1]}")
        check(all(same), f"{phase}: {nm} pages changed in the round trip")


def single_chip(kind: str) -> None:
    model_cfg = model_config()
    engine, reqs_o, res_o = serve_phase(
        "oracle", kind, engine_config(), POOL_BLOCKS)
    params = engine.params
    del engine

    engine, reqs_p, res_p = serve_phase(
        "pallas", kind, engine_config(use_pallas=True), POOL_BLOCKS, params)
    for C in (1, 16):
        check("tpu_custom_call" in engine.step_hlo(C),
              f"compiled step at C={C} holds no tpu_custom_call")
    print(f"[pallas] {kind}: compiled steps at C=1 and C=16 hold "
          f"tpu_custom_call")
    rng = np.random.default_rng(SEED)
    attention_parity(model_cfg, kind, rng)
    logit_parity(engine, kind, rng)
    same_req, same_tok = identical_share(output_pairs(
        reqs_o, res_o.outputs, reqs_p, res_p.outputs).values())
    print(f"[pallas] greedy outputs identical to oracle: {same_req:.0%} of "
          f"requests, {same_tok:.1%} of tokens (random weights: near-ties)")
    del engine

    engine, reqs_s, res_s = serve_phase(
        "swap", kind,
        engine_config(use_pallas=True, preemption_mode="swap"),
        SWAP_POOL_BLOCKS, params)
    mem = res_s.memory
    print(f"[swap] {mem.swap_preemptions} victims swapped out, "
          f"{mem.swap_restores} swap-ins, {mem.preemptions} preemptions")
    check(mem.swap_preemptions > 0, "no victim was swapped")
    same_req, same_tok = identical_share(output_pairs(
        reqs_o, res_o.outputs, reqs_s, res_s.outputs).values())
    print(f"[swap] greedy outputs identical to oracle: {same_req:.0%} of "
          f"requests, {same_tok:.1%} of tokens")
    page_round_trip("swap", engine, engine, rng)


def fleet(kind: str) -> None:
    """1 prefill + 3 decode replicas, one per chip, compiling lazily inside
    serving, against one engine on chip 0 with the same weights."""
    model_cfg = model_config()
    router = build_disagg(
        model_cfg, cfg=DisaggConfig(n_prefill=1, n_decode=3),
        engine_cfg=engine_config(use_pallas=True),
        sched_cfg=sched_config(), n_blocks=POOL_BLOCKS, prefix_cache=False,
    )
    reqs_f = make_requests(model_cfg.vocab_size)
    t0 = time.perf_counter()
    res_f = serve_disagg(reqs_f, router)
    fleet_s = time.perf_counter() - t0
    router.check_invariants()
    check_finished("fleet", reqs_f, res_f.outputs)
    homes = [d for rs in router.replicas for d in rs.engine.cache["k"].devices()]
    decode_prefill = sum(rs.sched.stats.scheduled_prefill_tokens
                         for rs in router.decode)
    print(f"[fleet] {kind} x4: compile+serve {fleet_s:.1f} s, "
          f"{res_f.rounds} rounds {res_f.replica_rounds}, handoffs "
          f"{res_f.handoffs}, decode-pool prefill tokens {decode_prefill}")
    print(f"[fleet] replica caches on devices {[d.id for d in homes]}")
    check(len(set(homes)) == len(router.replicas) == 4,
          f"replica caches share devices: {homes}")
    check(res_f.handoffs >= 1, "no request was handed off")
    check(decode_prefill == 0, "the decode pool scheduled prefill tokens")
    rng = np.random.default_rng(SEED)
    src = router.prefill[0].engine
    for rs in router.decode:
        page_round_trip(f"fleet {rs.name}", src, rs.engine, rng)
    decoded_on = {rs.name: list(rs.outputs) for rs in router.decode}
    params = src.params                            # on chip 0
    del router, src

    _, reqs_1, res_1 = serve_phase(
        "single", kind, engine_config(use_pallas=True), POOL_BLOCKS, params)
    pairs = output_pairs(reqs_f, res_f.outputs, reqs_1, res_1.outputs)
    same_req, same_tok = identical_share(pairs.values())
    print(f"[fleet] outputs identical to the single engine: {same_req:.0%} "
          f"of requests, {same_tok:.1%} of tokens")
    for name, rids in decoded_on.items():
        if rids:
            same_req, same_tok = identical_share([pairs[r] for r in rids])
            print(f"[fleet] {name}: {len(rids)} requests, identical to the "
                  f"single engine: {same_req:.0%} of requests, "
                  f"{same_tok:.1%} of tokens")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip replica fleet phase")
    args = ap.parse_args(argv)
    dev = require_tpu(args.chips)
    cache_dir = place_compile_cache()
    count = len(jax.devices())
    print(f"device_kind={dev.device_kind} devices={count} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    if args.chips == 4:
        fleet(dev.device_kind)
    else:
        single_chip(dev.device_kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
