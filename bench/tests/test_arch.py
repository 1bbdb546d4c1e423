"""The architecture seam (``bench/arch``): the dense file reproduces the
harness's dense weights, reference layer and FLOP count exactly as they were
before the seam, and a new architecture plugs in as one file and one
configuration (``data/moe_toy.py``, ``data/toy_moe.json``) and is served
and judged end to end."""
import hashlib
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, flops, modelcfg, reference, weights
from conftest import DATA, tiny_run

QWEN = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "qwen1.5-0.5b.json").read_text())
SEED = 2**31 + 7
# recorded from the harness before the seam (qwen1.5-0.5b's dims)
LAYER5_SHA256 = "30b116638322071ed38af4ede3f2b2e55b0a2eaf0db193466c33501ffaa5aacc"
FLOPS = {(0, 1, True): 927825920, (1000, 1, True): 1026129920,
         (0, 256, False): 161073856512, (3000, 256, True): 236882493440,
         (4095, 1, False): 1019215872}


def _before_layer_specs(d):
    """``bench/weights.py:_layer_specs`` before the seam, verbatim."""
    D, H, K, hd, F = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                      d["head_dim"], d["d_ff"])
    specs = [
        (None, "attn_norm", (D,), 0.1, 1.0),
        ("attn", "wq", (D, H, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wk", (D, K, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wv", (D, K, hd), 1 / math.sqrt(D), 0.0),
        ("attn", "wo", (H, hd, D), 1 / math.sqrt(H * hd), 0.0),
        (None, "ffn_norm", (D,), 0.1, 1.0),
        ("ffn", "w_gate", (D, F), 1 / math.sqrt(D), 0.0),
        ("ffn", "w_up", (D, F), 1 / math.sqrt(D), 0.0),
        ("ffn", "w_down", (F, D), 1 / math.sqrt(F), 0.0),
    ]
    if d["qkv_bias"]:
        specs += [("attn", "bq", (H, hd), 0.2, 0.0),
                  ("attn", "bk", (K, hd), 0.2, 0.0),
                  ("attn", "bv", (K, hd), 0.2, 0.0)]
    return specs


def _before_block(w, x, d, quant):
    """``bench/reference.py:_block`` before the seam, verbatim but for the
    shared pieces' names."""
    mm, rms, rope, attention = (reference.mm, reference.rms, reference.rope,
                                reference.attention)
    T, D = x.shape
    H, K, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = rms(x, w["attn_norm"], d["eps"])
    at = w["attn"]
    q = mm(a, at["wq"].reshape(D, H * hd), quant).reshape(T, H, hd)
    k = mm(a, at["wk"].reshape(D, K * hd), quant).reshape(T, K, hd)
    v = mm(a, at["wv"].reshape(D, K * hd), quant).reshape(T, K, hd)
    if "bq" in at:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    o = attention(q, k, v).reshape(T, H * hd)
    x = x + mm(o, at["wo"].reshape(H * hd, D), quant)
    a = rms(x, w["ffn_norm"], d["eps"])
    f = w["ffn"]
    h = jax.nn.silu(mm(a, f["w_gate"], quant)) * mm(a, f["w_up"], quant)
    return x + mm(h, f["w_down"], quant)


def _sha256(tree) -> str:
    m = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda kv: str(kv[0])):
        a = np.asarray(leaf)
        m.update(str(path).encode())
        m.update(str(a.dtype).encode())
        m.update(a.tobytes())
    return m.hexdigest()


def test_dense_weights_as_before():
    d = modelcfg.dims(QWEN)
    assert d["architecture"] == "dense"
    assert arch.of(d).layer_specs(d) == _before_layer_specs(d)
    shapes = jax.eval_shape(lambda: weights.full(d, 0))
    specs = {name: shape for _, name, shape, _, _ in _before_layer_specs(d)}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes["layers"])[0]:
        assert leaf.shape == (d["n_layers"],) + specs[path[-1].key]
        assert leaf.dtype == weights.DTYPE
    # one seeded layer at the published widths, as recorded before the seam
    assert _sha256(weights.layer(d, SEED, 5)) == LAYER5_SHA256


@pytest.mark.parametrize("quant", [False, True])
def test_dense_reference_layer_as_before(quant):
    d = modelcfg.dims(QWEN)
    w = weights.layer(d, SEED, 3)
    x = jax.random.normal(jax.random.PRNGKey(1), (reference.SEQ_BUCKET, d["d_model"]))
    got = reference._block(w, x, tuple(sorted(d.items())), quant)
    want = jax.jit(lambda w, x: _before_block(w, x, d, quant))(w, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dense_flops_as_before():
    d = modelcfg.dims(QWEN)
    for args, want in FLOPS.items():
        assert flops.chunk_flops(d, *args) == want


@pytest.fixture
def toy(tiny, monkeypatch):
    """The tiny cell on the toy architecture's configuration."""
    monkeypatch.setattr(arch, "ARCH_DIRS", [DATA] + arch.ARCH_DIRS)
    spec, cell = tiny["cell"]
    return dict(tiny, cell=(spec, dict(cell, config="toy_moe")))


def test_toy_architecture_flops_match_param_count(toy):
    cfg = modelcfg.load("toy_moe")
    d, mc = modelcfg.dims(cfg), modelcfg.program_config(cfg)
    assert mc.moe.n_experts == mc.moe.top_k == d["n_experts"]
    attn = 4 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    matmul_params = mc.param_count() - 2 * d["d_model"] * d["n_layers"]
    assert flops.chunk_flops(d, 0, 1, sampled=True) - attn == 2 * matmul_params


@pytest.mark.parametrize("seed", [3, 2**31 + 21])
def test_toy_architecture_served_and_judged(toy, seed):
    res = tiny_run(toy, seed=seed, control=True)
    assert res["correct"], res["checks"]
    assert res["info"]["compared"]["tokens"] >= 100
    assert not res["control"]["correct"]
