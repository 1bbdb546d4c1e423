"""A round with prefill chunks runs split: one decode row per slot plus
``P`` prefill rows of the bucket's width, instead of every slot padded to
that width.  On the same pool, tables, lengths and tokens, the split step
must give the padded step's logits (to float32 tolerance), sampled ids and
updated pages, in both pool layouts, on the oracle and the Pallas kernels
(interpret mode); and a served workload must come out token for token the
same with the split on or off."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.engine import engine as engine_mod
from repro.engine.engine import EngineConfig, JAXEngine, serve
from repro.engine.workload import WorkloadSpec, attach_prompt_tokens, sharegpt_like
from repro.models.model import build_model

TOL_F32 = 1e-5
B, PS, MAX_PAGES, C = 4, 16, 5, 16


def _f32_config():
    return dataclasses.replace(tiny_config("qwen1.5-0.5b"),
                               param_dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = _f32_config()
    m = build_model(cfg)
    return cfg, m.impl, m.init(jax.random.PRNGKey(0))


# per slot: ("decode", _) | ("prefill", chunk length) | ("idle", _); and P
ROUNDS = {
    "one-row": ([("decode", 1), ("prefill", 16), ("decode", 1), ("idle", 0)], 1),
    "two-rows": ([("decode", 1), ("prefill", 16), ("idle", 0), ("prefill", 9)], 2),
    "row-and-pad": ([("decode", 1), ("prefill", 12), ("decode", 1), ("idle", 0)], 2),
}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["oracle", "pallas"])
@pytest.mark.parametrize("kv_layout", ["split", "fused"])
@pytest.mark.parametrize("case", sorted(ROUNDS))
def test_split_round_matches_padded_round(model, case, kv_layout, use_pallas):
    cfg, impl, params = model
    rng = np.random.default_rng(5)
    lanes = cfg.n_kv_heads * cfg.resolved_head_dim
    n_phys = 2 * B * MAX_PAGES + 1
    names = ("kv",) if kv_layout == "fused" else ("k", "v")
    width = 2 * lanes if kv_layout == "fused" else lanes
    # a pool full of earlier context, tables scattered across it
    pool = {n: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, n_phys, PS, width)), jnp.float32) for n in names}
    tables = jnp.asarray(rng.permutation(n_phys - 1)[: B * MAX_PAGES]
                         .reshape(B, MAX_PAGES), jnp.int32)
    lens = jnp.asarray([20, 5, 33, 0], jnp.int32)

    slots, P = ROUNDS[case]
    draws = rng.integers(1, cfg.vocab_size, (B, C)).astype(np.int32)
    padded = np.zeros((B, C), np.int32)
    decode = np.zeros((B, 1), np.int32)
    pre_tokens = np.zeros((P, C), np.int32)
    pre_slot = np.full((P,), B, np.int32)          # B: a padding row
    chunk_lens = np.zeros((B,), np.int32)
    row = 0
    for s, (kind, n) in enumerate(slots):
        if kind == "idle":
            continue
        chunk_lens[s] = n
        padded[s, :n] = draws[s, :n]
        if kind == "decode":
            decode[s, 0] = draws[s, 0]
        else:
            pre_tokens[row, :n] = draws[s, :n]
            pre_slot[row] = s
            row += 1
    assert row <= P and (case != "row-and-pad" or row < P)

    knobs = dict(use_pallas=use_pallas, kv_layout=kv_layout)
    cl = jnp.asarray(chunk_lens)
    want, want_pool = impl.chunked_step_paged(
        params, jnp.asarray(padded), pool, lens, cl, tables, **knobs)
    got, got_pool = impl.chunked_step_paged(
        params, jnp.asarray(decode), pool, lens, cl, tables,
        jnp.asarray(pre_tokens), jnp.asarray(pre_slot), **knobs)

    live = chunk_lens > 0
    got, want = np.asarray(got)[live], np.asarray(want)[live]
    np.testing.assert_allclose(got, want, atol=TOL_F32, rtol=TOL_F32)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    for n in names:
        np.testing.assert_allclose(np.asarray(got_pool[n]),
                                   np.asarray(want_pool[n]),
                                   atol=TOL_F32, rtol=TOL_F32)


def test_rounds_split_only_past_the_measured_crossover():
    """At the benchmark cell's engine size (16 slots, the default buckets):
    on the oracle path a round splits only at the widest bucket and with at
    most 4 prefill rows (narrower chunks, or more of them, cost less padded
    on the chip); on the Pallas path at every bucket with fewer prefill rows
    than slots; the dense path never."""
    def engine(**kw):
        return JAXEngine(tiny_config("qwen1.5-0.5b"),
                         EngineConfig(n_slots=16, max_context=256, **kw))

    eng = engine()
    padded = [(C, 0) for C in eng.cfg.chunk_buckets]
    assert eng.round_shapes() == sorted(
        padded + [(256, 1), (256, 2), (256, 4)])
    assert [eng._split_rows(256, n) for n in range(1, 7)] == [1, 2, 4, 4, 0, 0]
    assert eng._split_rows(128, 1) == eng._split_rows(1, 0) == 0
    assert engine(use_pallas=True).round_shapes() == sorted(
        padded + [(C, P) for C in eng.cfg.chunk_buckets[1:]
                  for P in (1, 2, 4, 8)])
    assert engine(paged_kv=False).round_shapes() == padded


def _served(split: bool):
    cfg = _f32_config()
    # split wherever the chunks leave a slot out, or never
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(engine_mod.SPLIT_ROW_COST, "oracle", 0 if split else 1 << 30)
        eng = JAXEngine(cfg, EngineConfig(n_slots=8, max_context=160, seed=1))
    eng.warmup()
    reqs = sharegpt_like(WorkloadSpec(n_requests=10, inter_arrival_s=0.0,
                                      max_context=150, max_new_tokens=8,
                                      seed=9))
    attach_prompt_tokens(reqs, cfg.vocab_size, seed=9)
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=48, max_seqs=8))
    res = serve(reqs, sched, eng)
    assert res.report.n_finished == len(reqs)
    return [res.outputs[r.req_id] for r in reqs], eng.trace.counters


def test_serving_with_the_split_matches_padded_rounds():
    split_out, split_rounds = _served(split=True)
    padded_out, padded_rounds = _served(split=False)
    assert split_out == padded_out
    assert any(t != 0 for out in split_out for t in out)
    # (C, P) per round: the split ran with one and two prefill rows, and
    # the padded engine never split
    assert {0, 1, 2} <= {c[6] for c in split_rounds}
    assert {c[6] for c in padded_rounds} == {0}
    assert sum(c[3] for c in split_rounds) < sum(c[3] for c in padded_rounds)
