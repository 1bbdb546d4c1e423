"""Decoder-only transformer LM (dense / MoE / SWA / VLM families).

Layers are stored stacked (leading layer dim) and executed with
``jax.lax.scan`` so that 88-layer configs lower to a single compact HLO loop.
Supports three entry points:

  * ``train_logits``  — full-sequence logits (used by the training step)
  * ``prefill``       — forward + KV-cache construction, last-position logits
  * ``decode``        — one token with a padded (or SWA ring) KV cache
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L


def _block_init(rng, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {
        "attn_norm": jnp.ones((cfg.d_model,), dt),
        "attn": L.init_attention(ks[0], cfg),
        "ffn_norm": jnp.ones((cfg.d_model,), dt),
    }
    if cfg.moe is not None and cfg.moe.every == 1:
        p["moe"] = L.init_moe(ks[1], cfg)
        if cfg.moe.dense_residual:
            p["ffn"] = L.init_ffn(ks[2], cfg.d_model, cfg.d_ff, dt)
    else:
        p["ffn"] = L.init_ffn(ks[1], cfg.d_model, cfg.d_ff, dt)
    return p


def _block_ffn(p, x, cfg: ModelConfig):
    with jax.named_scope("ffn"):
        h = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if "moe" in p:
            moe_fn = L.moe_ffn_scatter if cfg.moe_impl == "scatter" else L.moe_ffn
            out = moe_fn(p["moe"], h, cfg)
            if "ffn" in p:  # arctic dense residual (parallel branch)
                out = out + L.ffn(p["ffn"], h)
        else:
            out = L.ffn(p["ffn"], h)
        return x + out


def _block_fwd(p, x, positions, cfg: ModelConfig, collect_kv: bool):
    """Full-sequence causal block (train / prefill)."""
    h = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = L.qkv_project(p["attn"], h, cfg, positions)
    attn = L.attention(
        q, k, v, q_offset=0, causal=True, sliding_window=cfg.sliding_window
    )
    # residual stream may be sequence-sharded (Megatron-SP style): norms and
    # residual adds then run on S/TP-sharded activations; GSPMD turns the
    # row-parallel matmuls' all-reduces into reduce-scatter + all-gather
    x = constrain(x + L.attn_output(p["attn"], attn, cfg),
                  ("batch", "act_seq", "embed"))
    x = constrain(_block_ffn(p, x, cfg), ("batch", "act_seq", "embed"))
    cache_axes = ("batch", "cache_seq", "cache_heads", "cache_hd")
    kv = (constrain(k, cache_axes), constrain(v, cache_axes)) if collect_kv else None
    return x, kv


def _block_decode(p, x, cache_k, cache_v, lens, cfg: ModelConfig, kv_positions=None):
    """Single-token block against a padded KV cache.

    cache_k/v: (B, S, Hkv, hd); lens: (B,) current lengths (write position for
    linear caches; for SWA ring caches the write slot is lens % W and
    ``kv_positions`` carries per-slot absolute positions).
    """
    B = x.shape[0]
    h = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k_new, v_new = L.qkv_project(p["attn"], h, cfg, lens[:, None])

    W = cache_k.shape[1]
    slot = lens % W if cfg.sliding_window else lens
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, slot].set(k_new[:, 0])
    cache_v = cache_v.at[bidx, slot].set(v_new[:, 0])

    if cfg.sliding_window:
        new_kv_positions = kv_positions.at[bidx, slot].set(lens)
        attn = L.attention(
            q, cache_k, cache_v,
            q_offset=lens, causal=True, sliding_window=cfg.sliding_window,
            kv_positions=new_kv_positions,
        )
    else:
        new_kv_positions = None
        attn = L.attention(q, cache_k, cache_v, q_offset=lens, kv_lens=lens + 1)
    x = x + L.attn_output(p["attn"], attn, cfg)
    x = _block_ffn(p, x, cfg)
    return x, cache_k, cache_v, new_kv_positions


class TransformerLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- params ------------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        k_emb, k_layers, k_head = jax.random.split(rng, 3)
        layer_rngs = jax.random.split(k_layers, cfg.n_layers)
        stacked = jax.vmap(lambda r: _block_init(r, cfg))(layer_rngs)
        params = {
            "embed": L.dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
            "layers": stacked,
            "final_norm": jnp.ones((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(
                k_head, (cfg.d_model, cfg.vocab_size), dt, scale=1.0 / math.sqrt(cfg.d_model)
            )
        return params

    # -- shared ------------------------------------------------------------
    def _embed_inputs(self, params, batch: Dict[str, Any]):
        cfg = self.cfg
        tok_emb = params["embed"][batch["tokens"]]  # (B, St, D) gather
        if cfg.n_patch_tokens and "patch_embeds" in batch:
            x = jnp.concatenate(
                [batch["patch_embeds"].astype(tok_emb.dtype), tok_emb], axis=1
            )
        else:
            x = tok_emb
        return constrain(x, ("batch", "seq", "embed"))

    def _unembed(self, params, x):
        with jax.named_scope("unembed"):
            if "lm_head" in params:
                return jnp.einsum("...d,dv->...v", x, params["lm_head"])
            return jnp.einsum("...d,vd->...v", x, params["embed"])

    def _run_layers(self, params, x, positions, collect_kv: bool, remat: bool):
        cfg = self.cfg

        def body(carry, lp):
            y, kv = _block_fwd(lp, carry, positions, cfg, collect_kv)
            return y, kv

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, kvs = jax.lax.scan(body, x, params["layers"])
        return x, kvs

    def unembed_weight(self, params):
        if "lm_head" in params:
            return params["lm_head"], "dv"
        return params["embed"], "vd"

    # -- entry points --------------------------------------------------------
    def train_hidden(self, params, batch: Dict[str, Any], remat: bool = True):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, _ = self._run_layers(params, x, positions, collect_kv=False, remat=remat)
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps)

    def train_logits(self, params, batch: Dict[str, Any], remat: bool = True):
        logits = self._unembed(params, self.train_hidden(params, batch, remat))
        return constrain(logits, ("batch", "seq", "vocab"))

    def prefill(self, params, batch: Dict[str, Any]):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, (ks, vs) = self._run_layers(params, x, positions, collect_kv=True, remat=False)
        x = L.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]

        if cfg.sliding_window:
            # Always return a W-sized ring cache so decode wraps correctly.
            W = cfg.sliding_window
            nL = ks.shape[0]
            if S >= W:
                pos = jnp.arange(S - W, S)
                slots = pos % W
                ks_r = jnp.zeros_like(ks[:, :, :W]).at[:, :, slots].set(ks[:, :, S - W:])
                vs_r = jnp.zeros_like(vs[:, :, :W]).at[:, :, slots].set(vs[:, :, S - W:])
                kv_pos = jnp.zeros((B, W), jnp.int32).at[:, slots].set(pos[None, :])
            else:
                pad = [(0, 0), (0, 0), (0, W - S), (0, 0), (0, 0)]
                ks_r = jnp.pad(ks, pad)
                vs_r = jnp.pad(vs, pad)
                kv_pos = jnp.full((B, W), L.INVALID_POS, jnp.int32).at[:, :S].set(
                    jnp.arange(S)[None, :]
                )
            cache = {
                "k": ks_r,
                "v": vs_r,
                "kv_pos": jnp.broadcast_to(kv_pos[None], (nL, B, W)),
            }
        else:
            cache = {"k": ks, "v": vs}  # (L, B, S, Hkv, hd)
        return logits, cache

    def decode(self, params, tokens, cache, lens):
        """tokens: (B, 1); cache k/v: (L, B, S, Hkv, hd); lens: (B,)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        x = constrain(x, ("batch", None, "embed"))

        has_pos = "kv_pos" in cache

        def body(carry, xs):
            if has_pos:
                lp, ck, cv, kp = xs
            else:
                lp, ck, cv = xs
                kp = None
            y, ck, cv, kp = _block_decode(lp, carry, ck, cv, lens, cfg, kv_positions=kp)
            return y, ((ck, cv, kp) if has_pos else (ck, cv))

        xs = (params["layers"], cache["k"], cache["v"])
        if has_pos:
            xs = xs + (cache["kv_pos"],)
        x, new = jax.lax.scan(body, x, xs)
        new_cache = {"k": new[0], "v": new[1]}
        if has_pos:
            new_cache["kv_pos"] = new[2]
        x = L.rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        return constrain(logits, ("batch", "vocab")), new_cache

    def chunked_step(self, params, tokens, cache, lens, chunk_lens,
                     *, use_pallas: bool = False):
        """One chunked-prefill engine round (Sarathi semantics, §3.1).

        The mixed batch is slot-aligned: every sequence slot advances by its
        ``chunk_lens[b]`` tokens this round — decode slots advance by 1 (their
        freshly sampled token), prefill slots by their scheduled chunk,
        inactive slots by 0.  tokens: (B, C) right-padded; cache k/v:
        (L, B, S+1, Hkv, hd) — the +1 row is a write sink for padding;
        lens: (B,) tokens already in cache; returns (logits_at_chunk_end,
        new_cache).

        Attention is the chunked-prefill kernel's exact computation: the
        chunk's queries attend to (prefix ‖ chunk) with a causal offset —
        ``use_pallas=True`` runs kernels/chunked_prefill_attention (interpret
        mode on CPU, Mosaic on TPU); False uses its jnp oracle.
        """
        from repro.kernels import ops as kops

        cfg = self.cfg
        assert not cfg.sliding_window, "engine demo path supports linear caches"
        B, C = tokens.shape
        S_pad = cache["k"].shape[2]          # S + 1 (padding sink row)
        positions = lens[:, None] + jnp.arange(C)[None, :]
        write_mask = jnp.arange(C)[None, :] < chunk_lens[:, None]
        # padding positions scatter into the sink row S_pad-1
        write_pos = jnp.where(write_mask, positions, S_pad - 1)
        kv_lens = lens + chunk_lens
        bidx = jnp.arange(B)

        x = params["embed"][tokens]
        x = constrain(x, ("batch", "seq", "embed"))

        def body(carry, xs):
            lp, ck, cv = xs
            h = L.rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
            q, k_new, v_new = L.qkv_project(lp["attn"], h, cfg, positions)
            ck = ck.at[bidx[:, None], write_pos].set(k_new)
            cv = cv.at[bidx[:, None], write_pos].set(v_new)
            attn = kops.prefill_chunk_attention(
                q, ck[:, :-1], cv[:, :-1], kv_lens, lens,
                use_pallas=use_pallas,
            )
            y = carry + L.attn_output(lp["attn"], attn, cfg)
            y = _block_ffn(lp, y, cfg)
            return y, (ck, cv)

        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        # logits at each slot's last chunk position (chunk_len-1; slot 0 for idle)
        last = jnp.maximum(chunk_lens - 1, 0)
        x_last = x[bidx, last]                       # (B, D)
        logits = self._unembed(params, x_last)
        return constrain(logits, ("batch", "vocab")), {"k": nk, "v": nv}

    def chunked_step_paged(self, params, tokens, kv_pages, lens, chunk_lens,
                           block_tables, pre_tokens=None, pre_slot=None, *,
                           use_pallas: bool = False,
                           pages_per_tile: int = 1,
                           kv_layout: str = "split",
                           buffering_depth: int = 1):
        """``chunked_step`` against a *paged* KV cache (vLLM layout).

        Same Sarathi round semantics and bit-level math as the dense path, but
        K/V live in a shared physical page pool ``(L, n_pages, page_size,
        Hkv*hd)`` (the engine's layout: each page row flat, so the kernels
        DMA it as stored; ``(.., Hkv, hd)`` works too) addressed through
        per-slot block tables ``(B, max_pages)`` instead of a
        ``(L, B, S+1, ...)`` slot-dense tensor.  New K/V for
        position ``p`` of slot ``b`` scatters to flat physical row
        ``block_tables[b, p // ps] * ps + p % ps``; padding positions scatter
        into the last physical page (the sink, which block tables also use as
        their pad value) and are never read back (``kv_lens`` masks them).

        A round is one or two groups of rows.  Alone, ``tokens (B, C)`` gives
        every slot one row of ``C`` tokens.  With ``pre_tokens (P, C)`` and
        ``pre_slot (P,)`` the round is split: ``tokens (B, 1)`` are the
        decode rows, and prefill row ``i`` carries the chunk of slot
        ``pre_slot[i]`` (``chunk_lens`` of that slot; its decode row is
        masked); a slot id ``>= B`` marks a padding row.  The groups meet
        only in attention: the embedding, projections, page scatter and FFN
        run once over the ``B + P*C`` rows, and each prefill row's
        last-position state takes its slot's row before the unembedding.

        ``kv_layout="fused"`` stores the pool head-interleaved
        (``kv_pages["kv"]: (L, n_phys, ps, 2*Hkv*hd)``, heads
        ``[K0,V0,K1,V1,...]``): the round's new K/V interleave into ONE
        scatter per layer and the attention kernel fetches each page's K+V
        with one DMA.  ``buffering_depth`` gathers run ahead of the kernels'
        dots (1 = synchronous).

        Attention is the paged chunked-prefill kernel for rows of ``C > 1``
        tokens and the paged flash-decode kernel for single-token rows; with
        ``use_pallas=False``, the tiled jnp prefill
        (``kernels/paged_prefill_tiled.py``) and the decode gather oracle.
        """
        from repro.kernels import ops as kops

        cfg = self.cfg
        assert not cfg.sliding_window, "engine demo path supports linear caches"
        fused = kv_layout == "fused"
        names = ("kv",) if fused else ("k", "v")
        n_phys, ps = kv_pages[names[0]].shape[1:3]
        sink = n_phys - 1
        B = tokens.shape[0]
        # each group: (tokens (R, C), lens (R,), chunk lens (R,), tables)
        groups = [(tokens, lens, chunk_lens, block_tables)]
        if pre_tokens is not None:
            def of_rows(a, fill):
                return a.at[pre_slot].get(mode="fill", fill_value=fill)
            groups = [
                (tokens, lens, chunk_lens.at[pre_slot].set(0, mode="drop"),
                 block_tables),
                (pre_tokens, of_rows(lens, 0), of_rows(chunk_lens, 0),
                 of_rows(block_tables, sink)),
            ]

        def geometry(toks, g_lens, g_chunk, g_tables):
            R, C = toks.shape
            positions = g_lens[:, None] + jnp.arange(C)[None, :]
            write_mask = jnp.arange(C)[None, :] < g_chunk[:, None]
            # logical position -> physical flat row via the block table
            page_of = g_tables[jnp.arange(R)[:, None], positions // ps]
            flat_pos = page_of * ps + positions % ps
            # padding positions scatter into the sink page
            write_pos = jnp.where(write_mask, flat_pos, sink * ps)
            return params["embed"][toks], positions, write_mask, write_pos

        def one_row(arrs):
            """Arrays of leading dims (R, C) as one row of sum(R*C)."""
            return jnp.concatenate(
                [a.reshape((-1,) + a.shape[2:]) for a in arrs])[None]

        per_group = [geometry(*g) for g in groups]
        if pre_tokens is None:
            x, positions, write_mask, write_pos = per_group[0]
        else:
            x, positions, write_mask, write_pos = map(one_row, zip(*per_group))
        x = constrain(x, ("batch", "seq", "embed"))
        kv_lens = [g[1] + g[2] for g in groups]

        if fused:
            decode_fn = kops.paged_flash_decode_attention_fused
            prefill_fn = kops.paged_prefill_chunk_attention_fused
        else:
            decode_fn = kops.paged_flash_decode_attention
            prefill_fn = kops.paged_prefill_chunk_attention
        knobs = dict(use_pallas=use_pallas, pages_per_tile=pages_per_tile,
                     buffering_depth=buffering_depth)

        def attend(q, pages):
            outs, start = [], 0
            for (toks, g_lens, _, g_tables), g_kv_lens in zip(groups, kv_lens):
                R, C = toks.shape
                if pre_tokens is None:
                    qg = q
                else:
                    qg = q[0, start:start + R * C].reshape((R, C) + q.shape[2:])
                    start += R * C
                if C == 1:
                    a = decode_fn(qg[:, 0], *pages, g_tables, g_kv_lens,
                                  **knobs)[:, None]
                else:
                    a = prefill_fn(qg, *pages, g_tables, g_kv_lens, g_lens,
                                   **knobs)
                outs.append(a)
            return outs[0] if pre_tokens is None else one_row(outs)

        def scatter(pages, new):
            with jax.named_scope("kv_write"):
                return pages.reshape(n_phys * ps, -1).at[write_pos].set(
                    new.reshape(write_pos.shape + (-1,))).reshape(pages.shape)

        def body(carry, xs):
            lp, pages = xs[0], xs[1:]       # pages: (n_phys, ps, lanes) each
            h = L.rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
            q, k_new, v_new = L.qkv_project(lp["attn"], h, cfg, positions)
            # masked lanes land in the SHARED sink page: write zeros, never
            # lane values — idle rows carry NaN (all-masked softmax, same as
            # the dense path) and a NaN parked in shared storage would
            # poison other rows' masked-position 0*V products downstream
            k_new = jnp.where(write_mask[:, :, None, None], k_new, 0)
            v_new = jnp.where(write_mask[:, :, None, None], v_new, 0)
            if fused:
                # interleave onto the head axis: ONE scatter writes K and V
                Hkv, hd = k_new.shape[2], k_new.shape[3]
                kv_new = jnp.stack([k_new, v_new], axis=3).reshape(
                    k_new.shape[:2] + (2 * Hkv, hd))
                pages = (scatter(pages[0], kv_new),)
            else:
                pages = (scatter(pages[0], k_new), scatter(pages[1], v_new))
            y = carry + L.attn_output(lp["attn"], attend(q, pages), cfg)
            y = _block_ffn(lp, y, cfg)
            return y, pages

        # layer_scan: the loop's own ops (each layer's weights and pages
        # sliced in, its updated pages stacked out, norms and residuals);
        # the scopes inside body name the rest
        with jax.named_scope("layer_scan"):
            x, new_pages = jax.lax.scan(
                body, x,
                (params["layers"],) + tuple(kv_pages[n] for n in names))
        new_cache = dict(zip(names, new_pages))
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if pre_tokens is None:
            last = jnp.maximum(chunk_lens - 1, 0)
            x_last = x[jnp.arange(B), last]          # (B, D)
        else:
            P, C = pre_tokens.shape
            pre_last = jnp.maximum(groups[1][2] - 1, 0)
            x_pre = x[0, B:].reshape(P, C, -1)[jnp.arange(P), pre_last]
            x_last = x[0, :B].at[pre_slot].set(x_pre, mode="drop")
        logits = self._unembed(params, x_last)
        return constrain(logits, ("batch", "vocab")), new_cache

    # -- cache/spec helpers ---------------------------------------------------
    def cache_struct(self, batch: int, seq_len: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        S = cfg.sliding_window if cfg.sliding_window else seq_len
        hd = cfg.resolved_head_dim
        shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, hd)
        c = {
            "k": jax.ShapeDtypeStruct(shape, dt),
            "v": jax.ShapeDtypeStruct(shape, dt),
        }
        if cfg.sliding_window:
            c["kv_pos"] = jax.ShapeDtypeStruct((cfg.n_layers, batch, S), jnp.int32)
        return c
