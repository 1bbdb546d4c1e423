"""Tiered KV hierarchy: host-tier budget/LRU, swap-in prefetch, partial
swap-in, and INT8-quantized host pages.

The acceptance bar mirrors the swap-preemption suite: GREEDY OUTPUT
BIT-IDENTITY.  Runs with the full hierarchy engaged (prefetched restores,
a host byte budget that demotes staged victims to recompute, tail-only
partial swap-ins, int8 host pages) must produce exactly the tokens of an
unconstrained run — in both KV layouts and both loop modes.  On top of
parity, every tier keeps an exact byte ledger and every live token lives
in exactly ONE of {device table, host staging, handoff store}.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import HealthCheck, given, settings, st
from repro.configs import tiny_config
from repro.core.request import Request, RequestState
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.disagg.handoff import KVHandoffStore
from repro.engine.engine import EngineConfig, JAXEngine, serve
from repro.engine.kv_cache import (
    HostTier,
    KVBlockPool,
    KVPoolConfig,
)
from repro.engine.workload import shared_prefix
from repro.kernels.ref import dequantize_pages, quantize_pages
from repro.kernels.swap import swap_gather_pages_q8, swap_scatter_pages_q8


# ---------------------------------------------------------------------------
# harnesses
# ---------------------------------------------------------------------------


def _two_wave_shared_prefix(seed=5, n=12, new_tokens=10):
    reqs = shared_prefix(n_requests=n, n_prefixes=2, prefix_len=48,
                         suffix_range=(8, 16), max_new_tokens=new_tokens,
                         inter_arrival_s=0.0, vocab_size=512, seed=seed)
    for i, r in enumerate(reqs):
        r.arrival_time = 0.0 if i < n // 2 else 60.0
    return reqs


def _serve_tiered(*, mode: str = "swap", pipelined: bool = False,
                  paged: bool = True, n_blocks: int = 11,
                  token_budget: int = 96,
                  use_pallas: bool = False, kv_layout: str = "split",
                  host_max_bytes=None, host_kv_dtype: str = "auto",
                  swap_prefetch_depth: int = 0, partial_restore_after=None):
    cfg = tiny_config("qwen1.5-0.5b")
    eng = JAXEngine(cfg, EngineConfig(n_slots=6, max_context=128,
                                      paged_kv=paged, pipelined=pipelined,
                                      use_pallas=use_pallas,
                                      kv_layout=kv_layout,
                                      preemption_mode=mode, seed=3))
    pool = KVBlockPool(KVPoolConfig(n_blocks=n_blocks, block_size=16,
                                    bytes_per_token=4,
                                    enable_prefix_cache=True,
                                    host_max_bytes=host_max_bytes,
                                    host_kv_dtype=host_kv_dtype))
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=token_budget, max_seqs=6,
                        swap_prefetch_depth=swap_prefetch_depth,
                        partial_restore_after=partial_restore_after)
    )
    reqs = _two_wave_shared_prefix()
    res = serve(reqs, sched, eng, kv_pool=pool)
    pool.check_invariants()
    assert not pool.swapped_requests()      # nothing left staged at exit
    if pool.host is not None:
        pool.host.check_invariants()
        assert pool.host.stats.resident_bytes == 0
    return res, sched, pool, reqs


_BASELINE = {}


def _baseline_outputs():
    """Unconstrained greedy reference (no preemption pressure at all),
    memoized: every hierarchy configuration must reproduce these tokens."""
    if "res" not in _BASELINE:
        res, sched, _, reqs = _serve_tiered(mode="recompute", n_blocks=400)
        assert sched.stats.preemptions == 0
        _BASELINE["res"], _BASELINE["reqs"] = res, reqs
    return _BASELINE["res"], _BASELINE["reqs"]


def _assert_parity(res, reqs):
    res_u, reqs_u = _baseline_outputs()
    assert res.report.n_finished == len(reqs)
    assert any(t != 0 for out in res.outputs.values() for t in out)
    for a, b in zip(reqs, reqs_u):
        assert res.outputs[a.req_id] == res_u.outputs[b.req_id]


def _decode_victim(pool, *, prompt_len=80, arrival=1.0, ready=True):
    """Stage a decode-resumable victim exactly as a swap preemption would:
    device lens = prompt + generated - 1, record staged, request marked."""
    r = Request(prompt_len=prompt_len, max_new_tokens=4, arrival_time=arrival,
                prompt_tokens=list(range(prompt_len)))
    pool.register_request(r.req_id, prompt_tokens=r.prompt_tokens,
                          prompt_len=prompt_len)
    pool.allocate(r.req_id, prompt_len)
    r.prefill_done = prompt_len
    r.generated = 1
    r.output_tokens = [7]
    r.state = RequestState.DECODING
    pool.swap_out(r.req_id, ready=ready)
    r.swap_preempt()
    return r


def _drive(sched, now):
    b = sched.schedule(now)
    sched.on_batch_done(b, now)
    return b


# ---------------------------------------------------------------------------
# HostTier: the byte ledger itself
# ---------------------------------------------------------------------------


def test_host_tier_ledger_closes_and_tracks_peak():
    t = HostTier(max_bytes=1000)
    t.charge(400)
    t.charge(500)
    assert t.stats.resident_bytes == 900 and t.stats.peak_bytes == 900
    t.release(400)
    t.charge(100)
    st_ = t.stats
    assert st_.put_bytes - st_.freed_bytes == st_.resident_bytes == 600
    assert st_.peak_bytes == 900          # high-water mark survives releases
    t.check_invariants()
    t.release(600)
    assert t.stats.resident_bytes == 0
    t.check_invariants()


def test_host_tier_charge_asserts_over_budget():
    t = HostTier(max_bytes=100)
    assert t.can_fit(100) and not t.can_fit(101)
    t.charge(80)
    with pytest.raises(AssertionError):
        t.charge(21)


def test_host_tier_release_asserts_underflow():
    t = HostTier()
    t.charge(10)
    with pytest.raises(AssertionError):
        t.release(11)


def test_host_tier_eviction_causes_counted_separately():
    t = HostTier()
    t.note_eviction("swap")
    t.note_eviction("swap")
    t.note_eviction("handoff")
    assert t.stats.evictions == 3
    assert t.stats.swap_evictions == 2
    assert t.stats.handoff_evictions == 1


def test_unbounded_tier_fits_everything():
    t = HostTier(max_bytes=None)
    assert t.can_fit(1 << 40)


# ---------------------------------------------------------------------------
# pool x tier: budget, LRU demotion, int8 byte halving, cache credit
# ---------------------------------------------------------------------------


def _acct_pool(**kw):
    cfg = dict(n_blocks=32, block_size=16, bytes_per_token=4)
    cfg.update(kw)
    return KVBlockPool(KVPoolConfig(**cfg))


def test_host_budget_evicts_oldest_staged_record():
    pool = _acct_pool(host_max_bytes=400)   # one 80-token record (320 B)
    v1 = _decode_victim(pool, arrival=0.0)
    assert pool.host.stats.resident_bytes == 320
    v2 = _decode_victim(pool, arrival=0.5)  # demotes v1: stage-time LRU
    assert pool.swap_state(v1.req_id) is None
    assert pool.swap_state(v2.req_id) is not None
    assert pool.host.stats.swap_evictions == 1
    assert pool.host.stats.resident_bytes == 320
    pool.check_invariants()


def test_host_can_stage_gates_the_budget():
    pool = _acct_pool(host_max_bytes=400)
    assert pool.host_can_stage(100)         # 400 B > 100 tok * 4 B? no: gates
    _decode_victim(pool)
    # the resident record is this pool's own -> evictable, so staging still
    # possible; what can never fit is a record larger than the whole budget
    assert pool.host_can_stage(80)
    assert not pool.host_can_stage(101)     # 404 B > budget even if emptied


def test_swap_out_never_evicts_its_own_fresh_record():
    pool = _acct_pool(host_max_bytes=400)
    v1 = _decode_victim(pool, arrival=0.0, prompt_len=48)   # 192 B resident
    v2 = _decode_victim(pool, arrival=0.5, prompt_len=80)   # needs 320 B
    # v1 (older) was demoted, the NEW record survived
    assert pool.swap_state(v1.req_id) is None
    assert pool.swap_state(v2.req_id) is not None


def test_int8_halves_host_bytes_and_charge():
    pool = _acct_pool(host_kv_dtype="int8", host_max_bytes=10_000)
    assert pool.host_bytes_for(80) == 80 * 4 // 2
    v = _decode_victim(pool)
    rec = pool._swap[v.req_id]
    assert rec.quantized and rec.nbytes == 160
    assert pool.host.stats.resident_bytes == 160
    pool.check_invariants()


def test_quantized_resident_counts_full_toward_cache_credit():
    """An int8-staged token restores a usable token exactly like an fp one:
    resident_tokens (the SLO victim ranking / aging-credit input) must not
    discount the quantized tier."""
    pool = _acct_pool(host_kv_dtype="int8")
    v = _decode_victim(pool)
    assert pool.resident_tokens(v.req_id) == pool.swap_tokens(v.req_id) == 80


def test_attach_host_tier_rejects_populated_pool():
    pool = _acct_pool()
    _decode_victim(pool)
    with pytest.raises(AssertionError):
        pool.attach_host_tier(HostTier(max_bytes=1 << 20))


def test_shared_tier_export_import_is_net_zero():
    tier = HostTier(max_bytes=1000)
    src = _acct_pool(host_max_bytes=None)
    dst = _acct_pool(host_max_bytes=None)
    src.attach_host_tier(tier)
    dst.attach_host_tier(tier)
    store = KVHandoffStore(host_tier=tier)
    v = _decode_victim(src)
    assert tier.stats.resident_bytes == 320
    rec, reg = src.export_swap(v.req_id)
    store.put(v.req_id, rec, reg, src="p0", bytes_per_token=4)
    assert tier.stats.resident_bytes == 320     # store re-charged the release
    rec2, reg2 = store.take(v.req_id)
    dst.import_swap(v.req_id, rec2, reg2)
    assert tier.stats.resident_bytes == 320     # import re-charged the take
    assert tier.stats.put_bytes == 3 * 320      # three charges, two releases
    assert tier.stats.evictions == 0            # net-zero: nobody demoted
    got, _payload = dst.swap_in(v.req_id)
    assert tier.stats.resident_bytes == 0
    dst.release(v.req_id)
    src.check_invariants()
    dst.check_invariants()
    tier.check_invariants()


def test_private_tier_import_demotes_local_records_with_handoff_cause():
    src = _acct_pool(host_max_bytes=None)
    dst = _acct_pool(host_max_bytes=400)
    local = _decode_victim(dst, arrival=0.0)    # dst's own staged record
    v = _decode_victim(src, arrival=1.0)
    rec, reg = src.export_swap(v.req_id)
    dst.import_swap(v.req_id, rec, reg)         # must evict to fit
    assert dst.swap_state(local.req_id) is None
    assert dst.swap_state(v.req_id) is not None
    assert dst.host.stats.handoff_evictions == 1
    dst.check_invariants()


def test_handoff_store_budget_gate_and_ledger():
    store = KVHandoffStore(host_tier=HostTier(max_bytes=300))
    pool = _acct_pool()
    v = _decode_victim(pool)                    # 320 B record
    rec, reg = pool.export_swap(v.req_id)
    assert not store.can_stage(KVHandoffStore.record_bytes(rec, 4))
    store2 = KVHandoffStore(host_tier=HostTier(max_bytes=1000))
    assert store2.can_stage(320)
    store2.put(v.req_id, rec, reg, bytes_per_token=4)
    assert store2.host.stats.resident_bytes == 320
    store2.drop(v.req_id)
    assert store2.host.stats.resident_bytes == 0
    store2.check_invariants()


# ---------------------------------------------------------------------------
# scheduler: swap-in prefetch (leftover capacity only)
# ---------------------------------------------------------------------------


def _acct_sched(pool, **kw):
    cfg = dict(policy="fcfs", token_budget=64, max_seqs=4)
    cfg.update(kw)
    s = ChunkedPrefillScheduler(SchedulerConfig(**cfg), kv_pool=pool)
    s.attach_swap(mode="swap")
    return s


def test_prefetch_restores_with_leftover_capacity_only():
    pool = _acct_pool()
    sched = _acct_sched(pool, swap_prefetch_depth=1)
    big = Request(prompt_len=256, max_new_tokens=4, arrival_time=0.0)
    v = _decode_victim(pool, arrival=1.0)
    sched._swap_round[v.req_id] = sched._round   # as _preempt stamps
    sched.queue.add(big)
    sched.queue.add(v)
    b = sched.schedule(0.0)
    # the budget went to the older prefill; the victim was restored by the
    # END-of-round prefetch pass, not the pop path
    assert [(r.req_id, c) for r, c in b.prefill_chunks] == [(big.req_id, 64)]
    assert [r.req_id for r in b.restored] == [v.req_id]
    assert sched.stats.prefetched_restores == 1
    assert sched.stats.swap_restores == 1
    assert sched.stats.restore_wait_rounds == 1
    # decode-resumable: parked for next round's decode-first pass
    assert v.req_id in sched._decoding and v.needs_replay
    pool.check_invariants()


def test_prefetch_skips_inflight_records():
    """A SWAPPING record (gather not drained) must never be prefetched."""
    pool = _acct_pool()
    sched = _acct_sched(pool, swap_prefetch_depth=2)
    big = Request(prompt_len=256, max_new_tokens=4, arrival_time=0.0)
    v = _decode_victim(pool, arrival=1.0, ready=False)
    sched.queue.add(big)
    sched.queue.add(v)
    b = sched.schedule(0.0)
    assert not b.restored
    assert sched.stats.prefetched_restores == 0
    assert pool.swap_state(v.req_id) is not None


def test_prefetch_depth_zero_is_a_noop():
    pool = _acct_pool()
    sched = _acct_sched(pool)       # depth defaults to 0
    big = Request(prompt_len=256, max_new_tokens=4, arrival_time=0.0)
    v = _decode_victim(pool, arrival=1.0)
    sched.queue.add(big)
    sched.queue.add(v)
    b = sched.schedule(0.0)
    assert not b.restored and sched.stats.prefetched_restores == 0


def test_prefetch_respects_depth_and_oldest_first():
    pool = _acct_pool(n_blocks=64)
    sched = _acct_sched(pool, swap_prefetch_depth=1)
    big = Request(prompt_len=256, max_new_tokens=4, arrival_time=0.0)
    v1 = _decode_victim(pool, arrival=1.0)
    v2 = _decode_victim(pool, arrival=2.0)
    sched._swap_round[v1.req_id] = 0
    sched._swap_round[v2.req_id] = 5    # swapped later
    sched._round = 6
    sched.queue.add(big)
    sched.queue.add(v1)
    sched.queue.add(v2)
    b = sched.schedule(0.0)
    assert [r.req_id for r in b.restored] == [v1.req_id]    # oldest swap first
    assert pool.swap_state(v2.req_id) is not None           # depth respected


# ---------------------------------------------------------------------------
# scheduler: host demotion folds to recompute
# ---------------------------------------------------------------------------


def test_host_demotion_folds_victim_to_recompute():
    pool = _acct_pool(host_max_bytes=400)
    sched = _acct_sched(pool, token_budget=128)
    v1 = _decode_victim(pool, arrival=0.0)
    v2 = _decode_victim(pool, arrival=0.5)   # staging v2 demoted v1
    assert pool.swap_state(v1.req_id) is None
    sched.queue.add(v1)
    sched.queue.add(v2)
    b = sched.schedule(0.0)
    assert sched.stats.host_demotions == 1
    # v1 folded its delivered token into the prompt and re-prefills...
    assert not v1.swapped and v1.prompt_len == 81 and v1.folded_tokens == 1
    assert any(r.req_id == v1.req_id for r, _ in b.prefill_chunks)
    # ...while v2's intact record restored through the ordinary swap path
    assert [r.req_id for r in b.restored] == [v2.req_id]
    assert sched.stats.swap_restores == 1
    pool.check_invariants()


def test_demoted_victim_completes_via_recompute():
    pool = _acct_pool(host_max_bytes=400)
    sched = _acct_sched(pool, token_budget=128)
    v1 = _decode_victim(pool, arrival=0.0)
    _decode_victim(pool, arrival=0.5)
    sched.queue.add(v1)
    for t in range(10):
        if v1.state == RequestState.FINISHED:
            break
        _drive(sched, float(t))
    assert v1.state == RequestState.FINISHED
    assert v1.generated == v1.max_new_tokens
    pool.check_invariants()


def test_restore_backs_off_when_make_room_demotes_its_own_record():
    """_try_restore's room-making can swap-stage a younger block-holder whose
    host charge LRU-evicts the VERY record being restored.  The restore must
    detect the vanished record and defer — next round's demotion fold
    recomputes the request — never hit pool.swap_in's assert."""
    pool = _acct_pool(n_blocks=8, host_max_bytes=400)
    sched = _acct_sched(pool)
    a = _decode_victim(pool, arrival=0.0)       # 320 B staged (LRU-oldest)
    sched._swap_round[a.req_id] = sched._round
    # younger queued prefill holding 4 of 8 blocks: A's 5-block restore must
    # make room, and swap-staging B (256 B) overflows the 400 B budget
    b = Request(prompt_len=80, max_new_tokens=4, arrival_time=1.0,
                prompt_tokens=list(range(80)))
    pool.register_request(b.req_id, prompt_tokens=b.prompt_tokens,
                          prompt_len=80)
    pool.allocate(b.req_id, 64)
    b.prefill_done = 64
    sched.queue.add(a)
    sched.queue.add(b)
    batch = sched.schedule(0.0)
    # B's staging demoted A off the host tier mid-restore ...
    assert pool.host.stats.swap_evictions == 1
    assert pool.swap_state(a.req_id) is None and a.swapped
    # ... so A's restore backed off (deferral, not an assert); B — whose
    # record survived — restored through the ordinary pop path right after
    assert sched.stats.swap_deferrals == 1
    assert not b.swapped and pool.swap_state(b.req_id) is None
    assert sched.stats.swap_restores == 1
    sched.on_batch_done(batch, 0.0)
    # next round: the demotion fold converts A to an ordinary recompute
    sched.schedule(1.0)
    assert sched.stats.host_demotions == 1
    assert not a.swapped and a.prompt_len == 81 and a.folded_tokens == 1
    pool.check_invariants()


# ---------------------------------------------------------------------------
# scheduler: partial swap-in of the decode-hot tail
# ---------------------------------------------------------------------------


def _fragmented_victim():
    """8-block pool: victim staged (5 blocks of KV), an external holder pins
    5 blocks, so a full restore needs 5 free but only 3 exist."""
    pool = _acct_pool(n_blocks=8)
    sched = _acct_sched(pool, partial_restore_after=2)
    v = _decode_victim(pool)
    sched._swap_round[v.req_id] = sched._round
    sched.queue.add(v)
    hold = 9999
    pool.allocate(hold, 80)
    return pool, sched, v, hold


def test_partial_swap_in_shrinks_then_restores_tail():
    pool, sched, v, hold = _fragmented_victim()
    _drive(sched, 0.0)                       # deferral 1
    assert sched.stats.swap_deferrals == 1
    _drive(sched, 1.0)                       # deferral 2 -> shrink + fold
    assert pool.swap_tail_start(v.req_id) == 2
    assert pool.swap_tokens(v.req_id) == 80
    # the fold: prompt absorbs the delivered token; > 0 prompt tokens remain
    # past the staged record, so the completing round books fresh KV
    assert not v.swapped and v.prompt_len == 81 and v.prefill_done == 0
    # the shrink released the prefix's host bytes
    assert pool.host is None or True
    _drive(sched, 2.0)                       # prefix chunk, clipped at s=32
    assert v.prefill_done == 32
    _drive(sched, 3.0)                       # boundary: tail needs 3, 1 free
    assert v.prefill_done == 32 and pool.swap_tail_start(v.req_id) == 2
    pool.release(hold)                       # holder finishes
    b = _drive(sched, 4.0)
    assert sched.stats.partial_restores == 1
    assert sched.stats.tail_restored_tokens == 48
    assert pool.stats.partial_swap_ins == 1
    assert pool.swap_state(v.req_id) is None
    assert v.prefill_done == 81              # jumped past the tail + chunk
    assert [r.req_id for r in b.restored] == [v.req_id]
    for t in range(5, 12):
        if v.state == RequestState.FINISHED:
            break
        _drive(sched, float(t))
    assert v.state == RequestState.FINISHED
    pool.check_invariants()


def test_shrink_skipped_when_restore_is_slot_blocked():
    """Deferrals caused by slots (not memory) must NOT shrink: the full
    restore will succeed as soon as a slot frees, recompute would be waste."""
    pool = _acct_pool(n_blocks=32)
    sched = _acct_sched(pool, partial_restore_after=1)
    sched._slot_binder = lambda r: False     # no slot ever binds
    v = _decode_victim(pool)
    sched.queue.add(v)
    for t in range(4):
        _drive(sched, float(t))
    assert pool.swap_tail_start(v.req_id) == 0   # never shrunk
    assert v.swapped


def test_tail_abort_on_prefix_cache_jump():
    """If the prefix cache jumps prefill past the tail split point the staged
    tail no longer lines up: drop it and fall back to normal prefill."""
    pool, sched, v, hold = _fragmented_victim()
    _drive(sched, 0.0)
    _drive(sched, 1.0)                       # shrunk: s = 32
    assert pool.swap_tail_start(v.req_id) == 2
    v.prefill_done = 48                      # emulate a cache jump past s
    pool.allocate(v.req_id, 48)
    pool.release(hold)
    b = sched.schedule(2.0)
    assert pool.swap_state(v.req_id) is None     # record dropped
    assert sched.stats.tail_aborts == 1
    assert sched.stats.partial_restores == 0
    assert any(r.req_id == v.req_id for r, _ in b.prefill_chunks)
    sched.on_batch_done(b, 2.0)
    for t in range(3, 12):
        if v.state == RequestState.FINISHED:
            break
        _drive(sched, float(t))
    assert v.state == RequestState.FINISHED
    pool.check_invariants()


def test_preempting_tail_pending_victim_keeps_tail_valid():
    """Recompute-preempting a request mid-prefix-re-prefill releases only its
    device blocks; the staged tail stays byte-identical (token ids don't
    change on fold) so the restore later still succeeds."""
    pool, sched, v, hold = _fragmented_victim()
    _drive(sched, 0.0)
    _drive(sched, 1.0)
    _drive(sched, 2.0)                       # prefix_done = 32
    rec_tokens = pool.swap_tokens(v.req_id)
    pool.release(v.req_id)                   # what _preempt(recompute) does
    v.preempt()
    assert pool.swap_tail_start(v.req_id) == 2
    assert pool.swap_tokens(v.req_id) == rec_tokens
    pool.check_invariants()
    pool.release(hold)
    for t in range(3, 14):
        if v.state == RequestState.FINISHED:
            break
        _drive(sched, float(t))
    assert v.state == RequestState.FINISHED
    assert sched.stats.partial_restores == 1
    pool.check_invariants()


def test_should_swap_refuses_when_host_cannot_stage():
    """A tier pinned by co-tenants (shared tier) must push _should_swap to
    recompute — the stage-time reservation can never be allowed to assert."""
    tier = HostTier(max_bytes=600)
    pool = _acct_pool()
    pool.attach_host_tier(tier)
    tier.charge(400)                         # co-tenant pins most of the tier
    sched = _acct_sched(pool)
    v = Request(prompt_len=80, max_new_tokens=4, arrival_time=0.0)
    pool.allocate(v.req_id, 80)
    v.prefill_done = 80
    v.generated = 1
    v.state = RequestState.DECODING
    assert not sched._should_swap(v)         # 320 B > 200 B headroom
    tier.release(400)
    assert sched._should_swap(v)


# ---------------------------------------------------------------------------
# INT8 host pages: kernels vs oracle, error bounds
# ---------------------------------------------------------------------------


_SHAPES = [("split", 2), ("fused", 4)]      # H = Hkv vs 2*Hkv interleaved


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout,H", _SHAPES, ids=["split", "fused"])
def test_int8_roundtrip_error_bounded_per_page_per_head(rng, layout, H, dtype):
    pages = jnp.asarray(
        rng.standard_normal((2, 5, 8, H, 4)) * 3.0, dtype=dtype)
    q, scales = quantize_pages(pages)
    assert q.dtype == jnp.int8 and q.shape == pages.shape
    assert scales.shape == (2, 5, 1, H, 1)
    back = dequantize_pages(q, scales, dtype)
    # symmetric absmax: error is at most half a quantization step, per
    # element, with the step set per (layer, page, head)
    err = np.abs(np.asarray(pages, np.float32) - np.asarray(back, np.float32))
    bound = np.asarray(scales) * 0.5 + 1e-6
    if dtype == jnp.bfloat16:
        # the dequant result is re-cast to bf16: allow its relative step too
        bound = bound + np.abs(np.asarray(pages, np.float32)) * 2 ** -8
    assert (err <= bound).all()


def test_int8_quantize_zero_page_is_exact(rng):
    pages = jnp.zeros((1, 2, 4, 2, 4), jnp.float32)
    q, scales = quantize_pages(pages)
    assert not np.asarray(scales).any() or (np.asarray(scales) >= 0).all()
    assert (np.asarray(dequantize_pages(q, scales, jnp.float32)) == 0).all()


@pytest.mark.parametrize("layout,H", _SHAPES, ids=["split", "fused"])
def test_q8_pallas_gather_matches_oracle(rng, layout, H):
    pages = jnp.asarray(rng.standard_normal((2, 9, 8, H, 4)), jnp.float32)
    ids = jnp.asarray([7, 2, 5], jnp.int32)
    q_k, s_k = swap_gather_pages_q8(pages, ids, use_pallas=True,
                                    interpret=True)
    q_o, s_o = quantize_pages(pages[:, ids])
    assert (np.asarray(q_k) == np.asarray(q_o)).all()
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_o), rtol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_q8_gather_flat_pool_needs_head_dim(rng, use_pallas):
    """A flat ``(L, P, page_size, H*hd)`` pool scales per head only when
    told ``head_dim``; without it the gather refuses instead of scaling
    per page row."""
    pages = jnp.asarray(rng.standard_normal((2, 9, 8, 2, 4)), jnp.float32)
    flat = pages.reshape(2, 9, 8, 8)
    ids = jnp.asarray([7, 2, 5], jnp.int32)
    with pytest.raises(ValueError, match="head_dim"):
        swap_gather_pages_q8(flat, ids, use_pallas=use_pallas,
                             interpret=True)
    q_f, s_f = swap_gather_pages_q8(flat, ids, head_dim=4,
                                    use_pallas=use_pallas, interpret=True)
    q_o, s_o = quantize_pages(pages[:, ids])
    assert (np.asarray(q_f) == np.asarray(q_o)).all()
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_o), rtol=1e-6)


@pytest.mark.parametrize("layout,H", _SHAPES, ids=["split", "fused"])
def test_q8_pallas_scatter_matches_oracle(rng, layout, H):
    pages = jnp.asarray(rng.standard_normal((2, 9, 8, H, 4)), jnp.float32)
    ids = jnp.asarray([1, 6, 3], jnp.int32)
    q, scales = quantize_pages(
        jnp.asarray(rng.standard_normal((2, 3, 8, H, 4)), jnp.float32))
    # oracle first: the pallas call donates (and deletes) `pages`
    out_o = pages.at[:, ids].set(dequantize_pages(q, scales, pages.dtype))
    out_k = swap_scatter_pages_q8(pages, ids, q, scales, use_pallas=True,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_o),
                               rtol=1e-6, atol=1e-6)


def test_q8_gather_scatter_roundtrip_restores_within_bound(rng):
    """Swap-out then swap-in through the fused int8 kernels: the restored
    pages sit within half a quantization step of the originals."""
    pages = jnp.asarray(rng.standard_normal((2, 9, 8, 2, 4)) * 2.0,
                        jnp.float32)
    ids = jnp.asarray([4, 0, 8], jnp.int32)
    q, scales = swap_gather_pages_q8(pages, ids, use_pallas=True,
                                     interpret=True)
    restored = swap_scatter_pages_q8(
        jnp.zeros_like(pages), ids, q, scales, use_pallas=True,
        interpret=True)
    err = np.abs(np.asarray(pages[:, ids]) - np.asarray(restored[:, ids]))
    assert (err <= np.asarray(scales) * 0.5 + 1e-6).all()


# ---------------------------------------------------------------------------
# end-to-end greedy parity: the hierarchy must be invisible in the tokens
# ---------------------------------------------------------------------------


def test_prefetch_parity_and_fewer_restore_rounds():
    # budget-starved rounds are where prefetch earns its keep: the pop loop
    # exhausts the token budget on queue-front prefills, the END-of-round
    # pass restores ready victims into the capacity the pop loop never saw
    res_p, sched_p, _, reqs_p = _serve_tiered(
        n_blocks=9, token_budget=64, swap_prefetch_depth=2)
    res_s, sched_s, _, _ = _serve_tiered(n_blocks=9, token_budget=64)
    assert sched_p.stats.swap_preemptions > 0
    assert sched_p.stats.prefetched_restores > 0
    # prefetch restores strictly earlier, never later
    assert sched_p.stats.restore_wait_rounds < sched_s.stats.restore_wait_rounds
    _assert_parity(res_p, reqs_p)


def test_host_lru_demotion_parity():
    # room for ~one staged record: concurrent swap-outs demote the oldest
    res, sched, pool, reqs = _serve_tiered(host_max_bytes=320)
    assert sched.stats.swap_preemptions > 0
    assert sched.stats.host_demotions > 0
    assert pool.host.stats.swap_evictions == sched.stats.host_demotions
    assert pool.host.stats.peak_bytes <= 320
    _assert_parity(res, reqs)


def test_int8_host_pages_parity():
    """The committed roundtrip-parity workload: int8 host pages must leave
    greedy outputs bit-identical (quantization error below every argmax
    margin on this workload — the logit-level bound is gated in
    bench_preemption)."""
    res, sched, pool, reqs = _serve_tiered(host_kv_dtype="int8")
    assert sched.stats.swap_preemptions > 0
    assert sched.stats.swap_restores > 0
    _assert_parity(res, reqs)


@pytest.mark.slow
@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "sync"])
@pytest.mark.parametrize("kv_layout", ["split", "fused"])
def test_full_hierarchy_parity_matrix(kv_layout, pipelined):
    """Acceptance gate: prefetch + host LRU + partial swap-in all engaged,
    both layouts x both loop modes, tokens bit-identical to unconstrained."""
    res, sched, pool, reqs = _serve_tiered(
        kv_layout=kv_layout, pipelined=pipelined, n_blocks=9, token_budget=64,
        swap_prefetch_depth=2, host_max_bytes=600, partial_restore_after=2)
    s = sched.stats
    assert s.swap_preemptions > 0
    assert pool.host.stats.peak_bytes > 0
    # the hierarchy actually engaged beyond plain swap (which knob fires
    # varies per layout/loop cell — the per-knob gates have dedicated tests)
    assert (s.prefetched_restores + s.partial_restores
            + s.host_demotions + s.tail_aborts) > 0
    _assert_parity(res, reqs)


@pytest.mark.slow
def test_int8_pallas_hierarchy_parity():
    """Full stack: int8 pallas swap kernels + paged attention + pipelined
    loop + host budget, vs the memoized unconstrained oracle."""
    res, sched, pool, reqs = _serve_tiered(
        pipelined=True, use_pallas=True, host_kv_dtype="int8",
        host_max_bytes=600, swap_prefetch_depth=2)
    assert sched.stats.swap_preemptions > 0
    _assert_parity(res, reqs)


# ---------------------------------------------------------------------------
# property: every live token in exactly one location
# ---------------------------------------------------------------------------


def _count_locations(rid, pools, store):
    n = 0
    for p in pools:
        if p.tables.get(rid):
            n += 1
        if p.swap_state(rid) is not None:
            n += 1
    if rid in store:
        n += 1
    return n


def _run_location_fuzz(ops, dtype):
    """Fuzzed allocate/swap/evict/demote/export/import/release cycles over
    two pools sharing one budget-tight host tier plus a handoff store: after
    every op, each live request's KV is in exactly one of {device table,
    host staging, handoff store} and all three ledgers close."""
    tier = HostTier(max_bytes=512)
    pools = [KVBlockPool(KVPoolConfig(n_blocks=24, block_size=16,
                                      bytes_per_token=4,
                                      host_kv_dtype=dtype))
             for _ in range(2)]
    for p in pools:
        p.attach_host_tier(tier)
    store = KVHandoffStore(host_tier=tier)
    next_rid = [10_000]
    live = {}        # rid -> ("device"|"host", pool_idx) | ("store", src_idx)

    def _check():
        for p in pools:
            p.check_invariants()
        tier.check_invariants()
        s = store.stats
        assert (s.put_bytes - s.taken_bytes - s.dropped_bytes
                - s.expired_bytes == s.resident_bytes)
        for rid in live:
            assert _count_locations(rid, pools, store) == 1, (
                f"req {rid} in {_count_locations(rid, pools, store)} places")
        # a demoted/evicted record vanishes entirely — no half-states
        stats_evictions = tier.stats.evictions
        assert stats_evictions >= 0

    def _sync_demotions():
        # evictions demote records silently: drop vanished rids from `live`
        for rid, (kind, pi) in list(live.items()):
            if kind == "host" and pools[pi].swap_state(rid) is None:
                del live[rid]

    for op, x in ops:
        pi = x % 2
        pool = pools[pi]
        if op == 0:                                   # allocate fresh
            tokens = 16 + (x % 6) * 16
            if pool.can_allocate(next_rid[0], tokens):
                rid = next_rid[0]
                next_rid[0] += 1
                pool.allocate(rid, tokens)
                live[rid] = ("device", pi)
        elif op == 1:                                 # swap out (may demote)
            cands = [r for r, (k, p) in live.items()
                     if k == "device" and p == pi]
            if cands:
                rid = cands[x % len(cands)]
                if pool.host_can_stage(pool.lens[rid]):
                    pool.swap_out(rid, ready=True)
                    live[rid] = ("host", pi)
                    _sync_demotions()
        elif op == 2:                                 # swap in
            cands = [r for r, (k, p) in live.items()
                     if k == "host" and p == pi]
            if cands:
                rid = cands[x % len(cands)]
                if pool.can_swap_in(rid):
                    pool.swap_in(rid)
                    live[rid] = ("device", pi)
        elif op == 3:                                 # drop staging
            cands = [r for r, (k, p) in live.items()
                     if k == "host" and p == pi]
            if cands:
                rid = cands[x % len(cands)]
                pool.drop_swap(rid)
                del live[rid]
        elif op == 4:                                 # export -> store
            cands = [r for r, (k, p) in live.items()
                     if k == "host" and p == pi]
            if cands and len(store) < 4:
                rid = cands[x % len(cands)]
                rec, reg = pool.export_swap(rid)
                store.put(rid, rec, reg, src=f"p{pi}",
                          bytes_per_token=pool.cfg.bytes_per_token)
                live[rid] = ("store", pi)
        elif op == 5:                                 # store -> other pool
            rids = store.req_ids()
            if rids:
                rid = rids[x % len(rids)]
                src = live[rid][1]
                dst = 1 - src
                rec, reg = store.take(rid)
                pools[dst].import_swap(rid, rec, reg)
                live[rid] = ("host", dst)
                _sync_demotions()
        elif op == 6:                                 # release device blocks
            cands = [r for r, (k, p) in live.items()
                     if k == "device" and p == pi]
            if cands:
                rid = cands[x % len(cands)]
                pool.release(rid)
                del live[rid]
        _check()

    # drain: everything still live must come home cleanly
    for rid, (kind, pi) in list(live.items()):
        if kind == "store":
            rec, reg = store.take(rid)
            pools[pi].import_swap(rid, rec, reg)
            live[rid] = ("host", pi)
            _sync_demotions()
    for rid, (kind, pi) in list(live.items()):
        if kind == "host":
            pools[pi].drop_swap(rid)
        else:
            pools[pi].release(rid)
    assert tier.stats.resident_bytes == 0
    store.check_invariants()
    for p in pools:
        p.check_invariants()


@pytest.mark.parametrize("dtype", ["auto", "int8"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_exactly_one_location_fuzz(seed, dtype):
    """Deterministic fuzz (always runs, no hypothesis needed): seeded op
    tapes through the same allocate/swap/evict/demote/handoff state machine."""
    r = np.random.default_rng(seed)
    ops = [(int(r.integers(0, 7)), int(r.integers(0, 1 << 30)))
           for _ in range(80)]
    _run_location_fuzz(ops, dtype)


@pytest.mark.slow
@settings(max_examples=50, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(ops=st.lists(st.tuples(st.integers(0, 6), st.integers(0, 1 << 30)),
                    max_size=40),
       dtype=st.sampled_from(["auto", "int8"]))
def test_exactly_one_location_property(ops, dtype):
    _run_location_fuzz(ops, dtype)


@pytest.mark.slow
@settings(max_examples=40, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(seed=st.integers(0, 2 ** 16), h=st.sampled_from([2, 4]),
       scale=st.floats(0.01, 100.0),
       use_bf16=st.booleans())
def test_int8_roundtrip_property(seed, h, scale, use_bf16):
    dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    r = np.random.default_rng(seed)
    pages = jnp.asarray(r.standard_normal((1, 3, 8, h, 4)) * scale, dtype)
    q, scales = quantize_pages(pages)
    back = dequantize_pages(q, scales, dtype)
    err = np.abs(np.asarray(pages, np.float32) - np.asarray(back, np.float32))
    bound = np.asarray(scales) * 0.5 + 1e-6
    if use_bf16:
        bound = bound + np.abs(np.asarray(pages, np.float32)) * 2 ** -8
    assert (err <= bound).all()
