import json
from pathlib import Path

import pytest

from bench import flops, modelcfg

CONFIGS = sorted((Path(__file__).resolve().parents[1] / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_flops_match_param_count(path):
    cfg = json.loads(path.read_text())
    d = modelcfg.dims(cfg)
    mc = modelcfg.program_config(cfg)
    # one token at position 0 that samples: every matmul parameter twice,
    # plus attention over one key
    attn = 4 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    embed_only = 0 if d["tied"] else d["vocab_size"] * d["d_model"]
    matmul_params = mc.param_count() - 2 * d["d_model"] * d["n_layers"] - embed_only
    assert flops.chunk_flops(d, 0, 1, sampled=True) - attn == 2 * matmul_params


def test_attention_grows_with_context():
    d = modelcfg.dims(json.loads(CONFIGS[0].read_text()))
    a = flops.chunk_flops(d, 1000, 1, sampled=False)
    b = flops.chunk_flops(d, 0, 1, sampled=False)
    assert a - b == 4 * d["n_heads"] * d["head_dim"] * d["n_layers"] * 1000
    # a chunk of n tokens from 0 sees 1 + 2 + ... + n keys
    c = flops.chunk_flops(d, 0, 4, sampled=False) - 4 * b
    assert c == 4 * d["n_heads"] * d["head_dim"] * d["n_layers"] * (10 - 4)
