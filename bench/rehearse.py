#!/usr/bin/env python3
"""Compile rehearsal of a configuration's serving step for a described
(not attached) v5e: the memory the step program needs at each chunk bucket,
so that a configuration's engine sizes can be chosen without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py qwen1.5-0.5b [n_slots max_context kv_blocks]
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --cell qwen05-fleet-1p3d

It compiles the composition the engine jits (``chunked_step_paged``, greedy
sampling and the length update, the page pool donated) at the file's sizes,
or the ones given, and prints ``memory_analysis()`` per bucket.  With
``--cell`` it takes the cell's configuration and compiles for each of its
replicas' devices of a described 2x2 v5e host (a fleet: one replica a
chip), so that every device's program and memory is seen before a
four-chip call.  The chip's
compiler refuses a program over the device's memory, so a size that
compiles here fits; leave room for what else the process holds.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def main() -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cell as cellmod, modelcfg
    from bench.run import load_cell
    from repro.models.model import build_model

    n_replicas = 1
    if sys.argv[1] == "--cell":
        _, cellspec = load_cell(sys.argv[2])
        cfg = modelcfg.load(cellspec["config"])
        layout = cellmod.layout(cellspec)
        n_replicas = 1 if layout is None else layout["prefill"] + layout["decode"]
    else:
        cfg = modelcfg.load(sys.argv[1])
    eng = cfg["engine"]
    B, S, n_blocks = ((int(x) for x in sys.argv[2:5]) if len(sys.argv) > 4 else
                      (eng["n_slots"], eng["max_context"], eng["kv_blocks"]))
    d, mc = modelcfg.dims(cfg), modelcfg.program_config(cfg)
    impl = build_model(mc).impl
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for device in topo.devices[:n_replicas]:
        compile_step(cfg, d, impl, SingleDeviceSharding(device), device.id,
                     B, S, n_blocks)


def compile_step(cfg, d, impl, sh, device_id, B, S, n_blocks) -> None:
    import jax
    import jax.numpy as jnp

    from bench import weights
    from repro.engine.sampler import SamplerConfig, sample_tokens

    def shaped(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)

    pshape = shaped(jax.eval_shape(lambda: weights.full(d, 0)))
    ps = 16
    pages = jax.ShapeDtypeStruct(
        (d["n_layers"], n_blocks + 1, ps, d["n_kv_heads"] * d["head_dim"]),
        jnp.bfloat16, sharding=sh)

    def step(params, tokens, cache, lens, chunk_lens, bt, last, use_last,
             smask, rng):
        col0 = jnp.arange(tokens.shape[1])[None, :] == 0
        tokens = jnp.where(use_last[:, None] & col0, last[:, None], tokens)
        logits, cache = impl.chunked_step_paged(
            params, tokens, cache, lens, chunk_lens, bt, use_pallas=False)
        toks = sample_tokens(logits, rng, SamplerConfig())
        return toks, cache, lens + chunk_lens, jnp.where(smask, toks, last)

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)

    def b(*s):
        return jax.ShapeDtypeStruct(s, jnp.bool_, sharding=sh)

    max_pages = -(-S // ps) + 1
    for C in (1, 256):
        t = time.time()
        m = jax.jit(step, donate_argnums=(2, 3, 6)).lower(
            pshape, i32(B, C), {"k": pages, "v": pages}, i32(B), i32(B),
            i32(B, max_pages), i32(B), b(B), b(B),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh),
        ).compile().memory_analysis()
        print(f"{cfg['name']} device={device_id} slots={B} max_context={S} "
              f"kv_blocks={n_blocks} C={C}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, "
              f"aliased {m.alias_size_in_bytes / 1e9:.2f} GB "
              f"({time.time() - t:.0f} s)", flush=True)


if __name__ == "__main__":
    main()
