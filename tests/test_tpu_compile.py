"""Compile-only rehearsal of the serving kernels for a TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached, so
these tests catch what interpret mode cannot — Mosaic's tiling and lane
alignment rules, VMEM limits — with no chip.  Shapes are Qwen1.5-0.5B's
serving widths (16 query and 16 kv heads of head_dim 64, 16-token pages,
a 2,049-page pool, 16 batch rows, 24 layers for the swap kernels) in the
flat page layout the engine stores.  Each kernel test asserts the kernel
was compiled by Mosaic (``tpu_custom_call``), not interpreted; the engine
round test compiles a 2-layer paged step and reads the shapes of its
float32 attention scores, one key tile of the served prefill attention.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

from repro.kernels.paged_decode_attention import (
    paged_decode_attention,
    paged_decode_attention_fused,
)
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention,
    paged_prefill_attention_fused,
)
from repro.kernels.paged_prefill_tiled import TILE_KEYS
from repro.kernels.swap import (
    swap_gather_pages,
    swap_gather_pages_q8,
    swap_scatter_pages,
    swap_scatter_pages_q8,
)
from repro.models.model import build_model

B, HQ, HKV, HD, PS, N_PAGES, MAX_PAGES, LAYERS = 16, 16, 16, 64, 16, 2049, 33, 24
LANES = HKV * HD


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e chip, with the persistent compile cache off: entries
    written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


_BF16, _I32 = jnp.bfloat16, jnp.int32
_TABLES = [((B, MAX_PAGES), _I32), ((B,), _I32)]


def _attention_case(kernel, C, fused):
    pools = [((N_PAGES, PS, 2 * LANES), _BF16)] if fused else \
        [((N_PAGES, PS, LANES), _BF16)] * 2
    if C == 1:
        q = [((B, HQ, HD), _BF16)]
        return (lambda *a: kernel(*a, interpret=False)), q + pools + _TABLES
    q = [((B, C, HQ, HD), _BF16)]
    return ((lambda *a: kernel(*a, interpret=False)),
            q + pools + _TABLES + [((B,), _I32)])


@pytest.mark.parametrize("layout", ["split", "fused"])
@pytest.mark.parametrize("C", [1, 16, 256], ids=["decode", "prefill16",
                                                 "prefill256"])
def test_paged_attention_compiles_for_v5e(one_chip, layout, C):
    fused = layout == "fused"
    if C == 1:
        kernel = paged_decode_attention_fused if fused else paged_decode_attention
    else:
        kernel = paged_prefill_attention_fused if fused else paged_prefill_attention
    fn, shapes = _attention_case(kernel, C, fused)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


_POOL = ((LAYERS, N_PAGES, PS, LANES), _BF16)
_IDS = ((32,), _I32)
_SWAP_CASES = {
    "gather": (lambda p, i: swap_gather_pages(
        p, i, use_pallas=True, interpret=False), [_POOL, _IDS]),
    "scatter": (lambda p, i, s: swap_scatter_pages(
        p, i, s, use_pallas=True, interpret=False),
        [_POOL, _IDS, ((LAYERS, 32, PS, LANES), _BF16)]),
    "gather_q8": (lambda p, i: swap_gather_pages_q8(
        p, i, head_dim=HD, use_pallas=True, interpret=False), [_POOL, _IDS]),
    "scatter_q8": (lambda p, i, q, s: swap_scatter_pages_q8(
        p, i, q, s, use_pallas=True, interpret=False),
        [_POOL, _IDS, ((LAYERS, 32, PS, HKV, HD), jnp.int8),
         ((LAYERS, 32, 1, HKV, 1), jnp.float32)]),
}


@pytest.mark.parametrize("case", sorted(_SWAP_CASES))
def test_swap_kernels_compile_for_v5e(one_chip, case):
    fn, shapes = _SWAP_CASES[case]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def _engine_step_text(sharding, P: int, use_pallas: bool = False):
    """The engine's paged round at Qwen1.5-0.5B's widths (2 layers) with
    256-token chunks: split into one decode row per slot and ``P`` prefill
    rows, or (``P = 0``) padded to one 256-token row per slot."""
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=2)
    model = build_model(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = ((cfg.n_layers, N_PAGES, PS, LANES), _BF16)
    head = [(((B, 1) if P else (B, 256)), _I32), pool, pool,
            ((B,), _I32), ((B,), _I32), ((B, MAX_PAGES), _I32)]
    pre = [((P, 256), _I32), ((P,), _I32)] if P else []
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in head + pre]

    def step(params, tokens, k, v, lens, chunk_lens, tables, *pre):
        return model.impl.chunked_step_paged(
            params, tokens, {"k": k, "v": v}, lens, chunk_lens, tables, *pre,
            use_pallas=use_pallas)

    return jax.jit(step).lower(params, *args).compile().as_text()


def _query_key_blocks(text, C, keys):
    """Element counts of the f32 tensors holding C queries against ``keys``
    keys (one tile of the served prefill attention's walk)."""
    out = []
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = [int(d) for d in dims.split(",")]
        if C in shape and keys in shape:
            out.append(math.prod(shape))
    return out


@pytest.mark.parametrize("split", [True, False], ids=["split", "padded"])
def test_mixed_round_scores_only_its_prefill_rows(one_chip, split):
    """The split round's float32 scores cover its one prefill row's 256
    queries against a tile of keys; none covers every slot's 256 queries,
    as the padded round's do."""
    keys = TILE_KEYS
    blocks = _query_key_blocks(_engine_step_text(one_chip, int(split)), 256,
                               keys)
    one_row = HQ * 256 * keys
    if split:
        assert blocks and max(blocks) == one_row
    else:
        assert max(blocks) >= B * one_row


@pytest.mark.parametrize("P", [1, 2, 4])
def test_split_round_compiles_pallas_kernels(one_chip, monkeypatch, P):
    """On the Pallas path a split round runs the decode kernel over every
    slot and the prefill kernel over its ``P`` rows in one program: both
    compile through Mosaic."""
    import repro.kernels

    # the kernels compile for the described chip, not for this host
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda *a: False)
    text = _engine_step_text(one_chip, P, use_pallas=True)
    assert text.count("tpu_custom_call") >= 2


def test_served_prefill_temporaries_fit_at_mistral_large_widths(one_chip):
    """The served prefill attention at mistral-large-123b's widths and the
    benchmark cell's sizes (8 rows of a C=256 chunk, 96 query heads over 8
    KV heads of 128, 4,624-key tables, a 1,601-page pool) needs a fraction
    of the gather oracle's temporaries, whose float32 scores span the whole
    table: that cell's step leaves about 1 GB of the chip free."""
    from repro.kernels import ops, ref

    B, Sq, Hq, Hkv, hd, n, mp = 8, 256, 96, 8, 128, 1601, 289
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in [
        ((B, Sq, Hq, hd), _BF16), ((n, PS, Hkv * hd), _BF16),
        ((n, PS, Hkv * hd), _BF16), ((B, mp), _I32), ((B,), _I32),
        ((B,), _I32)]]

    def served(*a):
        return ops.paged_prefill_chunk_attention(*a, use_pallas=False)

    def oracle(q, k, v, *rest):
        return ref.paged_prefill_attention_ref(
            q, k.reshape(n, PS, Hkv, hd), v.reshape(n, PS, Hkv, hd), *rest)

    def temp(fn):
        return jax.jit(fn).lower(*args).compile().memory_analysis().temp_size_in_bytes

    served_b, oracle_b = temp(served), temp(oracle)
    assert served_b < 1e9 and served_b < oracle_b / 4, (served_b, oracle_b)
