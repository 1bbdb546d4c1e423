"""The comparison that decides ``correct``, at the tiny CPU size: sound
runs pass it, the float8 control fails it, and a run whose timed path is
broken underneath comes out not correct.  The tiny limit (0.015) sits
between the program's readings (0.0016-0.0038) and the control's
(0.038-0.060) on seeds 1-3 here."""
import jax.numpy as jnp
import pytest

from conftest import tiny_run


def test_sound_run_is_correct_and_control_is_not(tiny):
    res = tiny_run(tiny, seed=2**31 + 3, control=True)
    limit = res["checks"]["max_logit_gap"]["limit"]
    assert res["correct"], res["checks"]
    assert res["info"]["compared"]["tokens"] >= 100
    assert not res["control"]["correct"]
    assert res["control"]["gap"] > limit


def _altered_sampler(orig):
    def sample(logits, rng, cfg):
        toks = orig(logits, rng, cfg)
        # every other row serves its second-best token: a token altered
        # where it is produced, inside the step
        second = jnp.argsort(logits, axis=-1)[:, -2].astype(toks.dtype)
        rows = jnp.arange(toks.shape[0]) % 2 == 1
        return jnp.where(rows, second, toks)
    return sample


def test_token_altered_where_produced_is_not_correct(tiny, monkeypatch):
    import repro.engine.engine as eng

    monkeypatch.setattr(eng, "sample_tokens", _altered_sampler(eng.sample_tokens))
    res = tiny_run(tiny, seed=7)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_step_that_keeps_its_cache_is_not_correct(tiny, monkeypatch):
    """The step returns its K/V pages unchanged: decoding then reads a
    cache that never holds the prompt."""
    from repro.models.transformer import TransformerLM

    orig = TransformerLM.chunked_step_paged

    def stale(self, params, tokens, kv_pages, *a, **kw):
        logits, _ = orig(self, params, tokens, kv_pages, *a, **kw)
        return logits, kv_pages

    monkeypatch.setattr(TransformerLM, "chunked_step_paged", stale)
    res = tiny_run(tiny, seed=8)
    assert not res["correct"]


@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_sound_runs_on_more_seeds(tiny, seed):
    res = tiny_run(tiny, seed=seed)
    assert res["correct"], res["checks"]
