"""Plain float32 reference of the served model, written from the published
description and importing nothing of the program: the embedding, one layer
at a time of the configuration's architecture (``bench/arch/<name>.py``,
built from the shared pieces here: RMSNorm, rotary embeddings with the
half-split rotation, grouped-query causal attention, float32 matmuls), the
final norm and the unembedding.

It runs one layer at a time over every sequence, making that layer's weights
from the seed (``bench.weights.layer``) and upcasting them to float32, so a
model whose float32 weights do not fit on the chip still fits.  Matmuls run
at ``precision=HIGHEST``: true float32 on a TPU.

``quant=True`` is the control: every matmul's operands are rounded to
float8 (e4m3, a per-tensor scale for weights and a per-row scale for
activations), the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, weights

HI = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 512       # sequences pad to a multiple of this (fewer compiles)
Q_BLOCK = 512          # attention query rows per block (bounds the scores)
ROW_BLOCK = 256        # unembedding rows per call
F8_MAX = 448.0         # largest finite float8_e4m3fn


def _f8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, w, quant: bool):
    """(T, K) @ (K, N) in float32."""
    if quant:
        a, w = _f8(a, -1), _f8(w, None)
    return jnp.dot(a, w, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (T, H, hd); rotate the two halves of each head by position."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention; q (T, H, hd), k/v (T, K, hd)."""
    T, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)          # query head h reads kv head h // g
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H, hd)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(w, x, d_items, quant: bool):
    """One layer of the configuration's architecture (``bench/arch``)."""
    d = dict(d_items)
    return arch.of(d).block(w, x, d, quant)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _scores(top, rows, cands, eps_arr, tied: bool, quant: bool):
    """Per row: best logit, its token, and the logits of ``cands``."""
    x = rms(rows, top["final_norm"].astype(jnp.float32), eps_arr)
    if tied:
        w = top["embed"].astype(jnp.float32).T
    else:
        w = top["lm_head"].astype(jnp.float32)
    logits = mm(x, w, quant)
    return (logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.take_along_axis(logits, cands, axis=-1))


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def score(d: dict, seed: int, seqs: Sequence[Sequence[int]],
          starts: Sequence[int], cands: Sequence[np.ndarray], *,
          quant: bool = False) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Run the model over each token sequence and read rows
    ``starts[i] .. len(seqs[i]) - 1``: for each such row the best logit, its
    token id, and the logits of ``cands[i]`` (``(rows, k)`` token ids).
    Sequences are right-padded; causal attention keeps padding out of every
    real row."""
    d_items = tuple(sorted(d.items()))
    top = weights.top(d, seed)
    xs = []
    for s in seqs:
        toks = np.zeros(_pad(len(s), SEQ_BUCKET), np.int32)
        toks[: len(s)] = s
        xs.append(_embed(top["embed"], jnp.asarray(toks)))
    for l in range(d["n_layers"]):
        w = weights.layer(d, seed, l)
        xs = [_block(w, x, d_items, quant) for x in xs]
        del w
    out = []
    eps = jnp.float32(d["eps"])
    for x, s, a, c in zip(xs, seqs, starts, cands):
        rows = x[a: len(s)]
        n = rows.shape[0]
        best, arg, cl = [], [], []
        for i in range(0, n, ROW_BLOCK):
            r = rows[i: i + ROW_BLOCK]
            cc = np.asarray(c[i: i + ROW_BLOCK], np.int32)
            m = r.shape[0]
            r = jnp.pad(r, ((0, ROW_BLOCK - m), (0, 0)))
            cc = np.pad(cc, ((0, ROW_BLOCK - m), (0, 0)))
            b, g, v = _scores(top, r, jnp.asarray(cc), eps, d["tied"], quant)
            best.append(np.asarray(b)[:m])
            arg.append(np.asarray(g)[:m])
            cl.append(np.asarray(v)[:m])
        out.append((np.concatenate(best), np.concatenate(arg),
                    np.concatenate(cl)))
    return out
