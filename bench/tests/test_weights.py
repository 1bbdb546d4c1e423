import jax
import numpy as np

from bench import weights

D = {"d_model": 32, "n_heads": 4, "n_kv_heads": 2, "head_dim": 8, "d_ff": 48,
     "n_layers": 3, "vocab_size": 64, "tied": False, "qkv_bias": True,
     "eps": 1e-6, "rope_theta": 1e4}


def test_layer_equals_slice_of_full():
    full = weights.full(D, 2**31 + 11)
    for l in range(D["n_layers"]):
        one = weights.layer(D, 2**31 + 11, l)
        got = jax.tree.map(lambda a: np.asarray(a[l]), full["layers"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
                     got, one)
    top = weights.top(D, 2**31 + 11)
    for k in top:
        np.testing.assert_array_equal(np.asarray(top[k]), np.asarray(full[k]))


def test_served_type_and_seed():
    a = weights.full(D, 1)
    b = weights.full(D, 2)
    assert all(x.dtype == weights.DTYPE for x in jax.tree.leaves(a))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(b["embed"]))
