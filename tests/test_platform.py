"""Platform plumbing that needs no chip: where the Pallas kernels may run
interpreted, ``chip_smoke.py``'s refusal to run anywhere but a TPU, the
compile-cache placement rule, and the engine's device placement."""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.engine.engine import EngineConfig, JAXEngine
from repro.kernels import interpret_mode, ops
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("platform,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_mode_only_on_cpu(platform, interpret):
    assert interpret_mode(platform) is interpret


def test_interpret_mode_refuses_other_platforms():
    with pytest.raises(RuntimeError, match="neither"):
        interpret_mode("gpu")


@pytest.mark.parametrize("kernel", ["decode", "swap"])
def test_ops_kernels_refuse_non_cpu_non_tpu(monkeypatch, kernel):
    """A Pallas call through ``kernels/ops.py`` on another platform raises
    instead of falling back to interpret mode (shapes unique to this test,
    so no earlier trace is reused)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    pages = jnp.zeros((3, 4, 24), jnp.float32)
    with pytest.raises(RuntimeError, match="neither"):
        if kernel == "decode":
            ops.paged_flash_decode_attention(
                jnp.zeros((1, 3, 8), jnp.float32), pages, pages,
                jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
                use_pallas=True)
        else:
            ops.gather_swap_pages(pages[None], jnp.zeros((2,), jnp.int32),
                                  use_pallas=True)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["1", "4"])
def test_chip_smoke_exits_nonzero_on_cpu_before_any_phase(monkeypatch, capsys,
                                                          argv):
    smoke = _load_chip_smoke()
    ran = []
    monkeypatch.setattr(smoke, "single_chip", lambda kind: ran.append(kind))
    monkeypatch.setattr(smoke, "fleet", lambda kind: ran.append(kind))
    with pytest.raises(SystemExit) as exc:
        smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert not ran
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_defers_to_environment(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.place_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{path.name}/" in ignored
    assert os.environ.get("JAX_COMPILATION_CACHE_DIR") is None


def test_engine_state_lives_on_its_device():
    dev = jax.devices()[-1]
    eng = JAXEngine(tiny_config("qwen1.5-0.5b"),
                    EngineConfig(n_slots=2, max_context=32), device=dev)
    arrays = [eng.lens, eng.last_token, eng.block_tables,
              *eng.cache.values(), *jax.tree_util.tree_leaves(eng.params)]
    assert all(a.devices() == {dev} for a in arrays)
    # the stored page rows are flat: kv_heads * head_dim lanes per token
    cfg = eng.model_cfg
    assert eng.cache["k"].shape[-1] == cfg.n_kv_heads * cfg.resolved_head_dim


def test_step_hlo_on_cpu_holds_no_mosaic_kernel():
    eng = JAXEngine(tiny_config("qwen1.5-0.5b"),
                    EngineConfig(n_slots=2, max_context=32, use_pallas=True,
                                 chunk_buckets=(1,)))
    text = eng.step_hlo(1)
    assert "ENTRY" in text and "tpu_custom_call" not in text
    assert np.asarray(eng.lens).tolist() == [0, 0]
