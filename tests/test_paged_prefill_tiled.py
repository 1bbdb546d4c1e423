"""The served paged chunked-prefill attention (``paged_prefill_tiled``, the
engine's ``use_pallas=False`` path) against the gather oracle in
``kernels/ref.py``: grouped-query heads, page-tile edges, packed rows with
their own prefixes, both pool layouts; and its compiled temporaries, which
must not grow with the block table's length as the oracle's scores do."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, paged_prefill_tiled, ref
from repro.kernels.paged_prefill_tiled import (
    paged_prefill_attention_tiled,
    paged_prefill_attention_tiled_fused,
)

PS, T, MAX_PAGES = 4, 8, 6           # tiles of 2 pages, 24 keys a row
SQ = 4
# per row: (kv_len, chunk length); the row's queries sit at kv_len - chunk..
KV_CASES = {
    "one-key": [(1, 1), (1, 1), (1, 1)],
    "tile-edge": [(T, SQ), (2 * T, 3), (T, 1)],
    "past-edge": [(T + 1, SQ), (2 * T + 1, 2), (T + 1, 1)],
    "full": [(MAX_PAGES * PS, SQ), (MAX_PAGES * PS, 1), (MAX_PAGES * PS, 3)],
}


def _setup(rng, Hq, Hkv, hd, dtype=jnp.float32):
    B = 3
    n_pages = 2 * B * MAX_PAGES + 1
    k_pages = jnp.asarray(rng.standard_normal((n_pages, PS, Hkv, hd)), dtype)
    v_pages = jnp.asarray(rng.standard_normal((n_pages, PS, Hkv, hd)), dtype)
    tables = jnp.asarray(rng.permutation(n_pages - 1)[:B * MAX_PAGES]
                         .reshape(B, MAX_PAGES), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, SQ, Hq, hd)), dtype)
    return q, k_pages, v_pages, tables


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(paged_prefill_tiled, "TILE_KEYS", T)


@pytest.mark.parametrize("layout", ["split", "fused"])
@pytest.mark.parametrize("case", sorted(KV_CASES))
@pytest.mark.parametrize("group,hd", [(1, 64), (12, 64), (12, 128), (1, 128)])
def test_tiled_prefill_matches_the_oracle(small_tiles, group, hd, case, layout):
    rng = np.random.default_rng(group * 1000 + hd)
    Hkv = 2
    q, k_pages, v_pages, tables = _setup(rng, group * Hkv, Hkv, hd)
    kv_lens = jnp.asarray([kv for kv, _ in KV_CASES[case]], jnp.int32)
    q_off = jnp.asarray([kv - c for kv, c in KV_CASES[case]], jnp.int32)
    want = ref.paged_prefill_attention_ref(q, k_pages, v_pages, tables,
                                           kv_lens, q_off)
    flat = lambda p: p.reshape(p.shape[0], PS, -1)      # the engine's layout
    if layout == "split":
        got = paged_prefill_attention_tiled(
            q, flat(k_pages), flat(v_pages), tables, kv_lens, q_off)
    else:
        got = paged_prefill_attention_tiled_fused(
            q, flat(ref.fuse_pages(k_pages, v_pages)), tables, kv_lens, q_off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_bf16_pool_matches_the_float32_oracle():
    """The engine's dtypes: bf16 queries and pages, float32 inside, one
    bf16 rounding of the output."""
    rng = np.random.default_rng(3)
    q, k_pages, v_pages, tables = _setup(rng, 24, 2, 128, jnp.bfloat16)
    kv_lens = jnp.asarray([23, 9, 17], jnp.int32)
    q_off = kv_lens - jnp.asarray([4, 2, 1], jnp.int32)
    want = ref.paged_prefill_attention_ref(q, k_pages, v_pages, tables,
                                           kv_lens, q_off)
    got = ops.paged_prefill_chunk_attention(
        q, k_pages.reshape(-1, PS, 256), v_pages.reshape(-1, PS, 256), tables,
        kv_lens, q_off, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_row_with_no_key_is_nan_as_in_the_oracle(small_tiles):
    rng = np.random.default_rng(4)
    q, k_pages, v_pages, tables = _setup(rng, 4, 2, 64)
    kv_lens = jnp.asarray([0, 5, 0], jnp.int32)
    q_off = jnp.asarray([0, 1, 0], jnp.int32)
    got = np.asarray(paged_prefill_attention_tiled(
        q, k_pages, v_pages, tables, kv_lens, q_off))
    want = np.asarray(ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, tables, kv_lens, q_off))
    assert np.isnan(got[[0, 2]]).all() and np.isnan(want[[0, 2]]).all()
    np.testing.assert_allclose(got[1], want[1], atol=1e-5, rtol=1e-5)


def _temp_bytes(fn, B, Sq, Hq, Hkv, hd, max_pages, n_pages=513):
    args = (jax.ShapeDtypeStruct((B, Sq, Hq, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((n_pages, 16, Hkv * hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((n_pages, 16, Hkv * hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    return jax.jit(fn).lower(*args).compile().memory_analysis().temp_size_in_bytes


def test_temporaries_do_not_grow_with_max_context():
    """On one pool, four times the block table (two tiles and eight) adds at most the gathered
    K/V of the extra pages to the served path's temporaries; the oracle's
    float32 scores and probabilities grow by far more."""
    B, Sq, Hq, Hkv, hd = 2, 64, 24, 2, 64

    def served(*a):
        return ops.paged_prefill_chunk_attention(*a, use_pallas=False)

    def oracle(q, k, v, bt, kv_lens, q_off):
        return ref.paged_prefill_attention_ref(
            q, k.reshape(k.shape[:2] + (Hkv, hd)), v.reshape(v.shape[:2] + (Hkv, hd)),
            bt, kv_lens, q_off)

    small, large = 64, 256              # pages a row: 2 and 8 tiles of 512 keys
    gathered = 2 * B * (large - small) * 16 * Hkv * hd * 2   # K and V, bf16
    grow = _temp_bytes(served, B, Sq, Hq, Hkv, hd, large) - _temp_bytes(
        served, B, Sq, Hq, Hkv, hd, small)
    oracle_grow = _temp_bytes(oracle, B, Sq, Hq, Hkv, hd, large) - _temp_bytes(
        oracle, B, Sq, Hq, Hkv, hd, small)
    assert grow <= gathered, (grow, gathered)
    assert oracle_grow > 4 * gathered, (oracle_grow, gathered)
