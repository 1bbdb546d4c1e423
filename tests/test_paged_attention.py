"""Paged-attention kernel parity: the block-table Pallas kernels (interpret
mode on CPU) and their gather oracles vs the DENSE reference on the same
logical K/V — across GQA group sizes, ragged ``kv_lens``, non-block-aligned
lengths, and permuted (non-contiguous) block tables — plus the engine-level
check that ``chunked_step_paged`` reproduces the dense ``chunked_step``
logits through a multi-round mixed schedule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.kernels import ref
from repro.kernels.paged_decode_attention import (
    paged_decode_attention,
    paged_decode_attention_fused,
)
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention,
    paged_prefill_attention_fused,
)
from repro.models.model import build_model

TOL_F32 = 1e-5
TOL_BF16 = 2e-2


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _paged_setup(rng, B, Hkv, hd, page_size, max_pages, dtype, permuted=True):
    """A physical page pool larger than needed, with per-sequence tables that
    scatter each sequence's pages non-contiguously across it."""
    n_pages = 2 * B * max_pages + 3
    k_pages = _rand(rng, (n_pages, page_size, Hkv, hd), dtype)
    v_pages = _rand(rng, (n_pages, page_size, Hkv, hd), dtype)
    ids = rng.permutation(n_pages - 1)[: B * max_pages] if permuted else \
        np.arange(B * max_pages)
    block_tables = jnp.asarray(ids.reshape(B, max_pages), jnp.int32)
    return k_pages, v_pages, block_tables


def _dense_view(pages, block_tables):
    """The logical per-sequence dense cache the tables describe."""
    return np.asarray(ref.gather_pages(pages, block_tables))


# ---------------------------------------------------------------------------
# paged flash-decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL_F32), (jnp.bfloat16, TOL_BF16)])
@pytest.mark.parametrize(
    "B,Hq,Hkv,hd,page_size,max_pages",
    [
        (1, 4, 4, 32, 16, 8),      # MHA
        (3, 8, 2, 64, 16, 6),      # GQA g=4
        (2, 8, 1, 32, 32, 4),      # MQA, bigger page
        (4, 16, 4, 16, 16, 5),     # engine tiny-config head_dim
        (2, 8, 4, 64, 16, 5),      # two 128-lane blocks of two heads
    ],
)
def test_paged_decode_vs_dense_reference(rng, dtype, tol, B, Hq, Hkv, hd,
                                         page_size, max_pages):
    q = _rand(rng, (B, Hq, hd), dtype)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, page_size, max_pages, dtype)
    # ragged, non-block-aligned valid lengths
    kv_lens = jnp.asarray(rng.integers(1, max_pages * page_size + 1, B), jnp.int32)

    out = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens)
    want = ref.decode_attention_ref(
        q, _dense_view(k_pages, bt), _dense_view(v_pages, bt), kv_lens
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_paged_decode_non_aligned_and_page_edges(rng):
    """Lengths straddling page boundaries: 1, ps-1, ps, ps+1, full."""
    B, Hq, Hkv, hd, ps, mp = 5, 4, 2, 32, 16, 4
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_lens = jnp.asarray([1, ps - 1, ps, ps + 1, mp * ps], jnp.int32)
    out = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens)
    want = ref.paged_decode_attention_ref(q, k_pages, v_pages, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


def test_paged_decode_layout_invariance(rng):
    """The same logical K/V under two different physical placements must give
    the same output — page indirection is pure data movement."""
    B, Hq, Hkv, hd, ps, mp = 2, 8, 2, 32, 16, 4
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_lens = jnp.asarray([37, 61], jnp.int32)
    out1 = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens)

    # re-scatter the same logical pages to fresh physical ids
    n_pages = k_pages.shape[0]
    perm = np.asarray(rng.permutation(n_pages))
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n_pages)
    out2 = paged_decode_attention(
        q, k_pages[perm], v_pages[perm], jnp.asarray(inv)[bt], kv_lens
    )
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("pages_per_tile", [1, 2, 4])
def test_paged_decode_pages_per_tile(rng, pages_per_tile):
    """Multi-page K/V tiles must be pure data movement: every tile width
    reproduces the gather oracle on ragged, NON-tile-aligned kv_lens, with
    max_pages not a multiple of the tile (exercises table padding)."""
    B, Hq, Hkv, hd, ps, mp = 5, 8, 2, 32, 16, 5     # 5 pages: pads for 2 and 4
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    # straddle page AND tile boundaries: 1, ps-1, one-past-tile, mid, full
    kv_lens = jnp.asarray(
        [1, ps - 1, pages_per_tile * ps + 1, 3 * ps + 7, mp * ps], jnp.int32
    )
    out = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens,
                                 pages_per_tile=pages_per_tile)
    want = ref.paged_decode_attention_ref(q, k_pages, v_pages, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


@pytest.mark.parametrize("pages_per_tile", [1, 2, 4])
def test_paged_prefill_pages_per_tile(rng, pages_per_tile):
    """Chunked-prefill parity for every tile width: causal offset + ragged
    non-aligned prefixes, max_pages not a multiple of the tile."""
    B, Sq, Hq, Hkv, hd, ps, mp = 3, 32, 8, 2, 32, 16, 5
    q = _rand(rng, (B, Sq, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    q_off = jnp.asarray([0, 7, mp * ps - Sq - 3], jnp.int32)   # non-aligned
    kv_lens = q_off + Sq
    out = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens, q_off,
                                  block_q=16, pages_per_tile=pages_per_tile)
    want = ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, bt, kv_lens, q_off
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


@pytest.mark.parametrize("pages_per_tile", [2, 4])
def test_paged_decode_tile_width_invariance(rng, pages_per_tile):
    """Tile width is a pure schedule knob: wider tiles must agree with the
    single-page kernel bit-for-bit up to accumulation tolerance."""
    B, Hq, Hkv, hd, ps, mp = 2, 4, 4, 32, 16, 8
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_lens = jnp.asarray([3 * ps + 5, mp * ps - 2], jnp.int32)
    a = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens,
                               pages_per_tile=1)
    b = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens,
                               pages_per_tile=pages_per_tile)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=TOL_F32, rtol=TOL_F32)


# ---------------------------------------------------------------------------
# double-buffered page DMA + fused head-interleaved layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("pages_per_tile", [1, 2])
def test_paged_decode_buffering_depth_invariance(rng, depth, pages_per_tile):
    """Buffering depth is a pure DMA-schedule knob: every depth must
    reproduce the gather oracle on ragged, non-tile-aligned kv_lens (a tail
    shorter than the prologue's lookahead included)."""
    B, Hq, Hkv, hd, ps, mp = 5, 8, 2, 32, 16, 5
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_lens = jnp.asarray(
        [1, ps - 1, pages_per_tile * ps + 1, 3 * ps + 7, mp * ps], jnp.int32
    )
    out = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens,
                                 pages_per_tile=pages_per_tile,
                                 buffering_depth=depth)
    want = ref.paged_decode_attention_ref(q, k_pages, v_pages, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_paged_prefill_buffering_depth_invariance(rng, depth):
    """Same for the chunked-prefill kernel: causal offset + ragged prefixes
    under every DMA lookahead depth."""
    B, Sq, Hq, Hkv, hd, ps, mp = 3, 32, 8, 2, 32, 16, 5
    q = _rand(rng, (B, Sq, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    q_off = jnp.asarray([0, 7, mp * ps - Sq - 3], jnp.int32)
    kv_lens = q_off + Sq
    out = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens, q_off,
                                  block_q=16, buffering_depth=depth)
    want = ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, bt, kv_lens, q_off
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


def test_fused_layout_roundtrip(rng):
    """fuse_pages interleaves K/V on the head axis; split_fused_pages must be
    its exact inverse (the layout is pure data movement)."""
    k = _rand(rng, (7, 16, 3, 32), jnp.float32)
    v = _rand(rng, (7, 16, 3, 32), jnp.float32)
    kv = ref.fuse_pages(k, v)
    assert kv.shape == (7, 16, 6, 32)
    k2, v2 = ref.split_fused_pages(kv)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("pages_per_tile", [1, 2])
def test_paged_decode_fused_layout(rng, depth, pages_per_tile):
    """The fused head-interleaved kernel (one DMA per page feeding both K
    and V) must agree with the split kernel and with its own oracle."""
    B, Hq, Hkv, hd, ps, mp = 4, 8, 2, 32, 16, 5
    q = _rand(rng, (B, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_pages = ref.fuse_pages(k_pages, v_pages)
    kv_lens = jnp.asarray([1, ps - 1, 3 * ps + 7, mp * ps], jnp.int32)
    out = paged_decode_attention_fused(q, kv_pages, bt, kv_lens,
                                       pages_per_tile=pages_per_tile,
                                       buffering_depth=depth)
    split = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens,
                                   pages_per_tile=pages_per_tile,
                                   buffering_depth=depth)
    want = ref.paged_decode_attention_fused_ref(q, kv_pages, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(split), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


@pytest.mark.parametrize("depth", [1, 2])
def test_paged_prefill_fused_layout(rng, depth):
    B, Sq, Hq, Hkv, hd, ps, mp = 3, 32, 8, 2, 32, 16, 5
    q = _rand(rng, (B, Sq, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_pages = ref.fuse_pages(k_pages, v_pages)
    q_off = jnp.asarray([0, 7, mp * ps - Sq - 3], jnp.int32)
    kv_lens = q_off + Sq
    out = paged_prefill_attention_fused(q, kv_pages, bt, kv_lens, q_off,
                                        block_q=16, buffering_depth=depth)
    split = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens, q_off,
                                    block_q=16, buffering_depth=depth)
    want = ref.paged_prefill_attention_fused_ref(q, kv_pages, bt, kv_lens, q_off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(split), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_fused_layout_many_lane_blocks(rng, kernel):
    """At head_dim 64 a fused page row splits into one 128-lane block per
    K+V head pair (four here, and two split): every block's offset and its
    K/V lanes must land on the right heads."""
    B, Sq, Hq, Hkv, hd, ps, mp = 2, 32, 8, 4, 64, 16, 5
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    kv_pages = ref.fuse_pages(k_pages, v_pages)
    if kernel == "decode":
        q = _rand(rng, (B, Hq, hd), jnp.float32)
        kv_lens = jnp.asarray([ps + 3, mp * ps], jnp.int32)
        out = paged_decode_attention_fused(q, kv_pages, bt, kv_lens)
        split = paged_decode_attention(q, k_pages, v_pages, bt, kv_lens)
        want = ref.paged_decode_attention_fused_ref(q, kv_pages, bt, kv_lens)
    else:
        q = _rand(rng, (B, Sq, Hq, hd), jnp.float32)
        q_off = jnp.asarray([7, mp * ps - Sq], jnp.int32)
        kv_lens = q_off + Sq
        out = paged_prefill_attention_fused(q, kv_pages, bt, kv_lens, q_off,
                                            block_q=16)
        split = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens,
                                        q_off, block_q=16)
        want = ref.paged_prefill_attention_fused_ref(q, kv_pages, bt, kv_lens,
                                                     q_off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(split), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


# ---------------------------------------------------------------------------
# paged chunked-prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL_F32), (jnp.bfloat16, TOL_BF16)])
@pytest.mark.parametrize(
    "B,Sq,Hq,Hkv,hd,page_size,max_pages,blk_q",
    [
        (1, 32, 4, 4, 32, 16, 6, 16),     # MHA
        (2, 64, 8, 2, 64, 16, 8, 32),     # GQA g=4
        (1, 16, 8, 1, 32, 32, 3, 16),     # MQA
        (3, 32, 16, 4, 16, 16, 4, 32),    # engine tiny-config head_dim
        (2, 32, 8, 4, 64, 16, 5, 16),     # two 128-lane blocks of two heads
    ],
)
def test_paged_prefill_vs_dense_reference(rng, dtype, tol, B, Sq, Hq, Hkv, hd,
                                          page_size, max_pages, blk_q):
    q = _rand(rng, (B, Sq, Hq, hd), dtype)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, page_size, max_pages, dtype)
    # random (non-aligned) prefix per row; kv valid = prefix + chunk
    q_off = jnp.asarray(
        rng.integers(0, max_pages * page_size - Sq + 1, B), jnp.int32
    )
    kv_lens = q_off + Sq

    out = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens, q_off,
                                  block_q=blk_q)
    want = ref.chunked_prefill_attention_ref(
        q, _dense_view(k_pages, bt), _dense_view(v_pages, bt), kv_lens, q_off
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_paged_prefill_zero_prefix(rng):
    """q_offset=0, kv == the chunk itself scattered across pages: causal
    self-attention through the block table."""
    B, Sq, Hq, Hkv, hd, ps, mp = 2, 32, 4, 4, 32, 16, 2
    q = _rand(rng, (B, Sq, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    q_off = jnp.zeros((B,), jnp.int32)
    kv_lens = jnp.full((B,), Sq, jnp.int32)
    out = paged_prefill_attention(q, k_pages, v_pages, bt, kv_lens, q_off,
                                  block_q=16)
    want = ref.paged_prefill_attention_ref(q, k_pages, v_pages, bt, kv_lens, q_off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL_F32, rtol=TOL_F32)


def test_paged_decode_equals_paged_prefill_single_token(rng):
    """A 1-token chunk through the prefill kernel must agree with the decode
    kernel — the engine dispatches between them by bucket size."""
    B, Hq, Hkv, hd, ps, mp = 3, 8, 2, 32, 16, 4
    q1 = _rand(rng, (B, 1, Hq, hd), jnp.float32)
    k_pages, v_pages, bt = _paged_setup(rng, B, Hkv, hd, ps, mp, jnp.float32)
    lens = jnp.asarray([5, 23, 64 - 1], jnp.int32)     # position of the token
    kv_lens = lens + 1
    a = paged_prefill_attention(q1, k_pages, v_pages, bt, kv_lens, lens,
                                block_q=1)
    b = paged_decode_attention(q1[:, 0], k_pages, v_pages, bt, kv_lens)
    np.testing.assert_allclose(np.asarray(a[:, 0]), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# engine step: paged vs dense chunked_step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas", [False, True])
def test_chunked_step_paged_matches_dense(use_pallas):
    """Multi-round mixed schedule (prefill chunks + decode) through
    ``chunked_step_paged`` with a permuted block table must reproduce the
    dense ``chunked_step`` logits — the layout changes, the math must not."""
    cfg = tiny_config("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    impl = model.impl
    B, S, bs = 2, 64, 16
    hd = cfg.resolved_head_dim
    rng = np.random.default_rng(11)
    tokens_all = rng.integers(1, cfg.vocab_size, (B, S))

    dense = {
        "k": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
        "v": jnp.zeros((cfg.n_layers, B, S + 1, cfg.n_kv_heads, hd), jnp.bfloat16),
    }
    max_pages = S // bs
    n_phys = 2 * B * max_pages + 1          # slack so tables can be permuted
    paged = {
        "k": jnp.zeros((cfg.n_layers, n_phys, bs, cfg.n_kv_heads, hd), jnp.bfloat16),
        "v": jnp.zeros((cfg.n_layers, n_phys, bs, cfg.n_kv_heads, hd), jnp.bfloat16),
    }
    ids = rng.permutation(n_phys - 1)[: B * max_pages]
    bt = jnp.asarray(ids.reshape(B, max_pages), jnp.int32)

    lens = jnp.zeros((B,), jnp.int32)
    # rounds: both prefill 16; slot0 decodes while slot1 prefills; both decode
    schedules = [
        (np.asarray([16, 16]), 16),
        (np.asarray([1, 16]), 16),
        (np.asarray([1, 1]), 1),
    ]
    pos = np.zeros((B,), int)
    for chunk_lens, C in schedules:
        toks = np.ones((B, C), np.int64)
        for b in range(B):
            c = chunk_lens[b]
            toks[b, :c] = tokens_all[b, pos[b] : pos[b] + c]
            pos[b] += c
        cl = jnp.asarray(chunk_lens, jnp.int32)
        ld, dense = impl.chunked_step(
            params, jnp.asarray(toks), dense, lens, cl, use_pallas=use_pallas
        )
        lp, paged = impl.chunked_step_paged(
            params, jnp.asarray(toks), paged, lens, cl, bt,
            use_pallas=use_pallas,
        )
        lens = lens + cl
        np.testing.assert_allclose(
            np.asarray(lp, np.float32), np.asarray(ld, np.float32),
            atol=2e-2, rtol=2e-2,       # bf16 cache, different gather order
        )
        assert (np.argmax(np.asarray(lp, np.float32), -1)
                == np.argmax(np.asarray(ld, np.float32), -1)).all()


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("depth", [1, 2])
def test_chunked_step_paged_fused_matches_split(use_pallas, depth):
    """The fused head-interleaved cache through the same multi-round mixed
    schedule must reproduce the split-layout logits EXACTLY (same dtype,
    same accumulation order — only the scatter/gather layout changes)."""
    cfg = tiny_config("qwen1.5-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    impl = model.impl
    B, S, bs = 2, 64, 16
    hd = cfg.resolved_head_dim
    rng = np.random.default_rng(11)
    tokens_all = rng.integers(1, cfg.vocab_size, (B, S))

    max_pages = S // bs
    n_phys = 2 * B * max_pages + 1
    split = {
        "k": jnp.zeros((cfg.n_layers, n_phys, bs, cfg.n_kv_heads, hd), jnp.bfloat16),
        "v": jnp.zeros((cfg.n_layers, n_phys, bs, cfg.n_kv_heads, hd), jnp.bfloat16),
    }
    fused = {
        "kv": jnp.zeros((cfg.n_layers, n_phys, bs, 2 * cfg.n_kv_heads, hd),
                        jnp.bfloat16),
    }
    ids = rng.permutation(n_phys - 1)[: B * max_pages]
    bt = jnp.asarray(ids.reshape(B, max_pages), jnp.int32)

    lens = jnp.zeros((B,), jnp.int32)
    schedules = [
        (np.asarray([16, 16]), 16),
        (np.asarray([1, 16]), 16),
        (np.asarray([1, 1]), 1),
    ]
    pos = np.zeros((B,), int)
    for chunk_lens, C in schedules:
        toks = np.ones((B, C), np.int64)
        for b in range(B):
            c = chunk_lens[b]
            toks[b, :c] = tokens_all[b, pos[b] : pos[b] + c]
            pos[b] += c
        cl = jnp.asarray(chunk_lens, jnp.int32)
        ls, split = impl.chunked_step_paged(
            params, jnp.asarray(toks), split, lens, cl, bt,
            use_pallas=use_pallas,
        )
        lf, fused = impl.chunked_step_paged(
            params, jnp.asarray(toks), fused, lens, cl, bt,
            use_pallas=use_pallas, kv_layout="fused", buffering_depth=depth,
        )
        lens = lens + cl
        np.testing.assert_allclose(
            np.asarray(lf, np.float32), np.asarray(ls, np.float32),
            atol=2e-5, rtol=2e-5,
        )
        assert (np.argmax(np.asarray(lf, np.float32), -1)
                == np.argmax(np.asarray(ls, np.float32), -1)).all()
        # the fused pool holds exactly the split pool's content, interleaved
        # on the head axis (even heads = K, odd heads = V)
        kv = np.asarray(fused["kv"], np.float32)
        np.testing.assert_array_equal(
            kv[:, :, :, 0::2], np.asarray(split["k"], np.float32))
        np.testing.assert_array_equal(
            kv[:, :, :, 1::2], np.asarray(split["v"], np.float32))
