"""Real-execution engine integration: chunked_step correctness vs whole-
prompt prefill, the serve loop, KV pool accounting, sampler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.request import Request, RequestState
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.engine import engine as engine_mod
from repro.engine.engine import EngineConfig, JAXEngine, serve
from repro.engine.kv_cache import KVBlockPool, KVPoolConfig
from repro.engine.sampler import SamplerConfig, sample_tokens
from repro.engine.workload import (
    WorkloadSpec,
    apc_heterogeneous,
    attach_prompt_tokens,
    sharegpt_like,
)
from repro.models.model import build_model


def test_chunked_step_equals_whole_prefill():
    """Splitting a prompt into chunks must produce the same final logits as
    prefilling it in one shot — the core correctness claim of chunked
    prefill (the schedule changes, the math must not)."""
    cfg = tiny_config("llama3.2-1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1, cfg.vocab_size)

    # whole-shot reference
    ref_logits, _ = model.prefill(params, {"tokens": tokens})

    # chunked: 3 rounds of 16 via chunked_step
    impl = model.impl
    hd = cfg.resolved_head_dim
    cache = {
        "k": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
        "v": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
    }
    lens = jnp.zeros((B,), jnp.int32)
    C = 16
    for i in range(3):
        chunk = tokens[:, i * C:(i + 1) * C]
        logits, cache = impl.chunked_step(
            params, chunk, cache, lens, jnp.full((B,), C, jnp.int32)
        )
        lens = lens + C

    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(ref_logits, np.float32),
        atol=0.25, rtol=0.05,  # bf16 accumulation-order tolerance
    )
    # argmax (the sampled token) must agree
    assert (np.argmax(np.asarray(logits, np.float32), -1)
            == np.argmax(np.asarray(ref_logits, np.float32), -1)).all()


def test_chunked_step_mixed_decode_and_prefill():
    """One round advancing a decode slot (chunk 1) and a prefill slot
    (chunk 16) together — Sarathi's mixed batch."""
    cfg = tiny_config("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    impl = model.impl
    B, S = 2, 64
    hd = cfg.resolved_head_dim
    cache = {
        "k": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
        "v": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
    }
    lens = jnp.zeros((B,), jnp.int32)
    # slot 0: prefill 16 tokens; slot 1: idle
    toks = jnp.ones((B, 16), jnp.int32)
    logits, cache = impl.chunked_step(
        params, toks, cache, lens, jnp.array([16, 0], jnp.int32)
    )
    lens = lens + jnp.array([16, 0])
    # now slot 0 decodes (chunk 1), slot 1 prefills 8
    toks2 = jnp.ones((B, 8), jnp.int32)
    logits2, cache = impl.chunked_step(
        params, toks2, cache, lens, jnp.array([1, 8], jnp.int32)
    )
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("policy", ["fcfs", "aging"])
def test_serve_end_to_end(policy):
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=8, max_context=256))
    reqs = sharegpt_like(WorkloadSpec(
        n_requests=6, inter_arrival_s=0.01, max_context=100,
        max_new_tokens=8, seed=7,
    ))
    attach_prompt_tokens(reqs, cfg.vocab_size)
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy=policy, token_budget=48, max_seqs=8)
    )
    res = serve(reqs, sched, eng, collect_samples=True)
    assert res.report.n_finished == 6
    assert all(len(res.outputs[r.req_id]) == r.max_new_tokens for r in reqs)
    feats, lats = res.samples
    assert feats.shape[1] == 16 and (lats > 0).all()


def test_serve_with_pallas_kernels():
    """Same serve loop with the Pallas chunked-prefill kernel (interpret)."""
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=4, max_context=128, use_pallas=True))
    reqs = sharegpt_like(WorkloadSpec(
        n_requests=2, inter_arrival_s=0.01, max_context=48,
        max_new_tokens=4, seed=9,
    ))
    attach_prompt_tokens(reqs, cfg.vocab_size)
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="aging", token_budget=32, max_seqs=4)
    )
    res = serve(reqs, sched, eng)
    assert res.report.n_finished == 2


# ---------------------------------------------------------------------------
# paged vs dense determinism (the tentpole's correctness claim)
# ---------------------------------------------------------------------------


def _two_wave_shared_prefix(seed=5):
    """shared_prefix in two deterministic waves: wave 1 all at t=0 (forces
    concurrency -> KV preemption on a small pool), wave 2 far behind it (the
    idle-gap jump admits it atomically AFTER wave 1 sealed its prefix blocks,
    so the prefix-restore path is exercised deterministically)."""
    from repro.engine.workload import shared_prefix
    reqs = shared_prefix(n_requests=12, n_prefixes=2, prefix_len=48,
                         suffix_range=(8, 16), max_new_tokens=10,
                         inter_arrival_s=0.0, vocab_size=512, seed=seed)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.0 if i < 6 else 60.0
    return reqs


def _serve_paged_or_dense(paged: bool):
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=6, max_context=128,
                                      paged_kv=paged, seed=3))
    # 11 blocks cannot hold the shared prefixes plus 6 growing decode tails
    # (prefix sharing kicks in even within a wave: later binders hit the
    # first binder's sealed blocks): preemption forced
    pool = KVBlockPool(KVPoolConfig(n_blocks=11, block_size=16,
                                    bytes_per_token=4,
                                    enable_prefix_cache=True))
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=96, max_seqs=6)
    )
    reqs = _two_wave_shared_prefix()
    res = serve(reqs, sched, eng, kv_pool=pool)
    pool.check_invariants()
    return res, sched, pool, reqs


def test_paged_and_dense_greedy_outputs_identical_with_preemption():
    """Greedy-sampled outputs of the paged engine must be identical to the
    dense engine's on a shared-prefix workload — including after forced KV
    preemptions and across prefix-cache restores (paged restores are
    zero-copy: the matched pages are still resident)."""
    res_p, sched_p, pool_p, reqs_p = _serve_paged_or_dense(paged=True)
    res_d, sched_d, pool_d, reqs_d = _serve_paged_or_dense(paged=False)
    # the adversarial conditions actually happened, in both layouts
    assert sched_p.stats.preemptions > 0 and sched_d.stats.preemptions > 0
    assert pool_p.stats.hit_tokens > 0 and pool_d.stats.hit_tokens > 0
    assert res_p.report.n_finished == res_d.report.n_finished == 12
    # the comparison must be over REAL sampled ids, not placeholder zeros
    assert any(t != 0 for out in res_p.outputs.values() for t in out)
    # req_ids are globally assigned: match requests by workload position
    for rp, rd in zip(reqs_p, reqs_d):
        assert res_p.outputs[rp.req_id] == res_d.outputs[rd.req_id], (
            rp.req_id, rd.req_id,
        )


# ---------------------------------------------------------------------------
# warmup coverage: the first serving round never pays a cold compile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_layout,depth",
                         [("split", 1), ("split", 2),
                          ("fused", 1), ("fused", 2)],
                         ids=["split-d1", "split-d2", "fused-d1", "fused-d2"])
def test_warmup_covers_every_configured_shape(kv_layout, depth):
    """After ``warmup(include_swap=True)`` a pressured serve — every chunk
    bucket, split rounds of one and two prefill rows, forced swap-outs and
    restores — must add ZERO new entries to the engine step's jit cache or
    the swap kernels', for every configured ``(kv_layout,
    buffering_depth)``: no serving round ever eats a cold XLA compile."""
    from repro.kernels.swap import swap_gather_pages, swap_scatter_pages

    cfg = tiny_config("qwen1.5-0.5b")
    # split every mixed round the split shapes hold (P = 1, 2, 4 of 6 slots)
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(engine_mod.SPLIT_ROW_COST, "oracle", 0)
        eng = JAXEngine(cfg, EngineConfig(n_slots=6, max_context=128,
                                          paged_kv=True, pipelined=True,
                                          kv_layout=kv_layout,
                                          buffering_depth=depth,
                                          preemption_mode="swap", seed=3))
    pool = KVBlockPool(KVPoolConfig(n_blocks=11, block_size=16,
                                    bytes_per_token=4,
                                    enable_prefix_cache=True))
    # bind BEFORE warmup: the pool's geometry shapes the cache array
    eng.bind_kv_pool(pool)
    eng.warmup(include_swap=True)
    n_step = eng._step._cache_size()
    n_gather = swap_gather_pages._cache_size()
    n_scatter = swap_scatter_pages._cache_size()

    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=96, max_seqs=6)
    )
    reqs = _two_wave_shared_prefix()
    res = serve(reqs, sched, eng, kv_pool=pool)
    assert res.report.n_finished == len(reqs)
    assert sched.stats.swap_preemptions > 0        # pressure actually bit
    # split rounds of one and two prefill rows ran: (C, P) per round
    shapes = {(c[5], c[6]) for c in eng.trace.counters}
    assert {0, 1, 2} <= {P for _, P in shapes}
    assert shapes <= set(eng.round_shapes())
    assert eng._step._cache_size() == n_step == len(eng.round_shapes())
    assert swap_gather_pages._cache_size() == n_gather
    assert swap_scatter_pages._cache_size() == n_scatter


# ---------------------------------------------------------------------------
# late slot binding (slot lifecycle regression)
# ---------------------------------------------------------------------------


def _rate_limited_setup(n_slots=2):
    from repro.tenancy import FairnessConfig, TenantSpec
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=n_slots, max_context=128))
    fc = FairnessConfig(
        tenants=(
            TenantSpec("limited", rate_tokens_per_s=200.0, burst_tokens=50.0),
            TenantSpec("free"),
        ),
        admission_policy="queue",
    )
    sched = ChunkedPrefillScheduler(SchedulerConfig(
        policy="fcfs", token_budget=64, max_seqs=n_slots, fairness=fc,
    ))
    limited = [Request(prompt_len=40, max_new_tokens=2, arrival_time=0.0,
                       tenant="limited") for _ in range(5)]
    free = [Request(prompt_len=16, max_new_tokens=4,
                    arrival_time=0.001 * (i + 1), tenant="free")
            for i in range(3)]
    return cfg, eng, sched, limited, free


def test_delayed_admissions_pin_no_slots():
    """Regression (ROADMAP slot-lifecycle bug): a rate-limited tenant's
    delayed backlog used to receive engine slots at admission and hold them
    while parked, exhausting ``n_slots``.  Slots now bind at first schedule,
    so the delay pen pins nothing and other tenants schedule immediately."""
    _cfg, eng, sched, limited, free = _rate_limited_setup()
    sched.attach_slot_binder(eng.acquire_slot, releaser=eng.release)
    for r in limited + free:
        assert sched.submit(r)          # over-budget ones are parked, not rejected
    delayed = [r for r in limited if sched.queue.is_delayed(r)]
    assert len(delayed) >= 3            # the backlog exceeds n_slots=2
    batch = sched.schedule(0.0)
    scheduled = {r.req_id for r, _ in batch.prefill_chunks}
    # the free tenant got a slot this very round, through the parked backlog
    assert scheduled & {r.req_id for r in free}
    # no delay-parked request holds an engine slot
    assert not any(r.req_id in eng.slot_of for r in delayed)
    assert len(eng.slot_of) <= 2


def test_zero_progress_deferral_unbinds_slot():
    """A request that binds a slot but cannot allocate a single KV token
    (pool held by a strictly-older request: no eligible victim) must NOT pin
    the slot while deferred — it unbinds and re-binds when it can run."""
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=2, max_context=128))
    pool = KVBlockPool(KVPoolConfig(n_blocks=4, block_size=16, bytes_per_token=4))
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=64, max_seqs=2), kv_pool=pool
    )
    eng.bind_kv_pool(pool)
    sched.attach_slot_binder(eng.acquire_slot, releaser=eng.release)
    old = Request(prompt_len=60, max_new_tokens=2, arrival_time=0.0)
    young = Request(prompt_len=32, max_new_tokens=2, arrival_time=1.0)
    sched.submit(old)
    sched.submit(young)
    batch = sched.schedule(0.0)
    # old's chunk takes the whole pool; young bound a slot, got a zero chunk
    # (no strictly-younger victim exists), and must have been unbound again
    assert [(r.req_id, c) for r, c in batch.prefill_chunks] == [(old.req_id, 60)]
    assert old.req_id in eng.slot_of
    assert young.req_id not in eng.slot_of
    assert len(eng.free_slots) == 1
    # drain: old finishes, its blocks free, young re-binds and completes
    now, rounds = 0.0, 0
    sched.on_batch_done(batch, 0.01)
    while sched.has_work() and rounds < 100:
        now += 0.01
        rounds += 1
        b = sched.schedule(now)
        if not b.is_empty():
            sched.on_batch_done(b, now)
    assert old.state == RequestState.FINISHED
    assert young.state == RequestState.FINISHED
    pool.check_invariants()


def test_rate_limited_backlog_does_not_starve_other_tenants_e2e():
    """End-to-end serve(): with 5 delayed requests from a rate-limited tenant
    against 2 engine slots, the unlimited tenant's requests all finish, and
    they get service ahead of the parked backlog's tail."""
    cfg, eng, sched, limited, free = _rate_limited_setup()
    reqs = limited + free
    attach_prompt_tokens(reqs, cfg.vocab_size)
    res = serve(reqs, sched, eng, max_rounds=6000)
    assert all(r.state == RequestState.FINISHED for r in free)
    assert res.report.n_finished == 8   # the backlog itself drains too
    assert max(r.ttft() for r in free) < max(r.ttft() for r in limited)


# ---------------------------------------------------------------------------
# KV pool
# ---------------------------------------------------------------------------


def test_kv_pool_alloc_release_cycle():
    pool = KVBlockPool(KVPoolConfig(n_blocks=10, block_size=16, bytes_per_token=4))
    assert pool.can_allocate(1, 100)          # 7 blocks
    pool.allocate(1, 100)
    assert pool.used_blocks == 7
    pool.allocate(1, 12)                      # fits in block 7
    assert pool.used_blocks == 7
    pool.allocate(1, 10)                      # crosses into block 8
    assert pool.used_blocks == 8
    assert not pool.can_allocate(2, 40)       # needs 3, only 2 free
    pool.release(1)
    assert pool.used_blocks == 0
    assert pool.can_allocate(2, 160)


def test_kv_pool_exhaustion_raises():
    pool = KVBlockPool(KVPoolConfig(n_blocks=2, block_size=16))
    with pytest.raises(MemoryError):
        pool.allocate(1, 100)


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


def test_sampler_greedy():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, -1.0]])
    out = sample_tokens(logits, jax.random.PRNGKey(0), SamplerConfig())
    assert list(np.asarray(out)) == [1, 0]


def test_sampler_topk_restricts_support():
    logits = jnp.asarray([[0.0, 10.0, 9.0, -50.0]] * 64)
    out = sample_tokens(
        logits, jax.random.PRNGKey(0),
        SamplerConfig(temperature=1.0, top_k=2),
    )
    assert set(np.asarray(out).tolist()) <= {1, 2}


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def test_sharegpt_like_is_skewed_and_seeded():
    spec = WorkloadSpec(n_requests=500, seed=4)
    a = sharegpt_like(spec)
    b = sharegpt_like(spec)
    assert [r.prompt_len for r in a] == [r.prompt_len for r in b]
    ps = np.asarray([r.prompt_len for r in a])
    assert np.percentile(ps, 50) < 60          # short median
    assert np.percentile(ps, 90) > 90          # heavy tail


def test_apc_heterogeneous_ratio():
    reqs = apc_heterogeneous(n_requests=500, seed=1)
    short = sum(1 for r in reqs if r.prompt_len <= 50)
    long_ = sum(1 for r in reqs if r.prompt_len >= 200)
    assert short + long_ == 500
    assert abs(short / 500 - 0.98) < 0.02      # 49:1
