"""Random weights from ``--seed``, made on the device in the type they are
served in (bfloat16), in the layout the program's ``TransformerLM`` takes:
the embedding and final norm here, each layer's leaves as the configuration's
architecture lists them (``bench/arch``).

Each leaf draws from its own key, and each layer of a stacked leaf from
``fold_in(leaf key, layer)``, so ``layer(seed, l)`` gives exactly the slice
``full(seed)[...][l]``: the reference makes one layer at a time and never
needs the program's arrays.  Scales follow the usual initialisation
(``1/sqrt(fan_in)``, embedding 0.02); norm weights and QKV biases are drawn
around their defaults so that their code paths are exercised.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import arch

DTYPE = jnp.bfloat16


def _key(seed: int):
    return jax.random.PRNGKey(seed % (2**32 - 1))


def _normal(key, shape, std, mean):
    return (jax.random.normal(key, shape, jnp.float32) * std + mean).astype(DTYPE)


def _layer(d: dict, key, l):
    """Layer ``l``'s leaves as the architecture lists them
    (``bench/arch/<name>.py:layer_specs``)."""
    out: dict = {}
    for i, (group, name, shape, std, mean) in enumerate(arch.of(d).layer_specs(d)):
        k = jax.random.fold_in(jax.random.fold_in(key, 100 + i), l)
        leaf = _normal(k, shape, std, mean)
        if group is None:
            out[name] = leaf
        else:
            out.setdefault(group, {})[name] = leaf
    return out


def _top(d: dict, key):
    D, V = d["d_model"], d["vocab_size"]
    top = {
        "embed": _normal(jax.random.fold_in(key, 0), (V, D), 0.02, 0.0),
        "final_norm": _normal(jax.random.fold_in(key, 1), (D,), 0.1, 1.0),
    }
    if not d["tied"]:
        top["lm_head"] = _normal(jax.random.fold_in(key, 2), (D, V),
                                 1 / math.sqrt(D), 0.0)
    return top


def _hashable(d: dict):
    return tuple(sorted(d.items()))


@functools.partial(jax.jit, static_argnums=0)
def _full(dims_items, key):
    d = dict(dims_items)
    params = _top(d, key)
    params["layers"] = jax.vmap(lambda l: _layer(d, key, l))(
        jnp.arange(d["n_layers"]))
    return params


@functools.partial(jax.jit, static_argnums=0)
def _one_layer(dims_items, key, l):
    return _layer(dict(dims_items), key, l)


@functools.partial(jax.jit, static_argnums=0)
def _top_only(dims_items, key):
    return _top(dict(dims_items), key)


def full(d: dict, seed: int):
    """Every weight, in one jitted call on the default device."""
    return _full(_hashable(d), _key(seed))


def layer(d: dict, seed: int, l: int):
    """Layer ``l``'s weights alone (equal to ``full(d, seed)["layers"][..][l]``)."""
    return _one_layer(_hashable(d), _key(seed), jnp.int32(l))


def top(d: dict, seed: int):
    """Embedding, final norm and (untied) unembedding."""
    return _top_only(_hashable(d), _key(seed))
