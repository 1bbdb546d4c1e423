"""mistral-large-123b — [dense] 88L d_model=12288 96H (GQA kv=8) d_ff=28672 vocab=32768.

[hf:mistralai/Mistral-Large-Instruct-2407, config.json] Checked key by key:
``MistralForCausalLM``, ``hidden_size`` 12288, ``intermediate_size`` 28672,
``num_hidden_layers`` 88, ``num_attention_heads`` 96, ``num_key_value_heads``
8, head_dim 128 (= 12288 / 96), ``vocab_size`` 32768,
``max_position_embeddings`` 131072, ``rms_norm_eps`` 1e-05, ``rope_theta``
1e6, ``tie_word_embeddings`` false, ``hidden_act`` silu, ``sliding_window``
null; no attention biases.  The defaults of ``ModelConfig`` carry head_dim,
the norm epsilon, untied embeddings, no biases and full attention.

123B params: TP=16 alone leaves ~15.4 GB of weights per chip (v5e has 16 GB),
so the multi-chip launchers (``launch/dryrun.py``, ``launch/train.py``) use
2D weight sharding (``sharding="fsdp_tp"``) with per-layer gather.  Serving
runs each replica on one device and does not read ``sharding``.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mistral-large-123b",
    family="dense",
    n_layers=88,
    d_model=12_288,
    n_heads=96,
    n_kv_heads=8,
    d_ff=28_672,
    vocab_size=32_768,
    rope_theta=1_000_000.0,
    sharding="fsdp_tp",
    subquadratic=False,
    notes="123B dense; 2D weight sharding in the multi-chip launchers",
)
