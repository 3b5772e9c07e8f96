"""IBM Granite-3.0 2B base [hf:ibm-granite/granite-3.0-2b-base]: dense GQA.

Source: https://huggingface.co/ibm-granite/granite-3.0-2b-base (config.json,
``model_type: granite``).  40L, d_model=2048, 32 heads (GQA kv=8,
head_dim=64), d_ff=8192, vocab=49155, 4096 positions.  SwiGLU, tied
embeddings, RoPE theta 10k, RMSNorm eps 1e-5.  The four Granite multipliers:
``embedding_multiplier`` 12, ``attention_multiplier`` 0.015625 (scores are
q.k / 64, not q.k / 8), ``residual_multiplier`` 0.22, ``logits_scaling`` 8.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite_3_2b",
    family="dense",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=49155,
    layer_pattern=("attn",),
    mlp_kind="swiglu",
    tie_embeddings=True,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    attn_scale=0.015625,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    microbatch_per_device=2,
    supports_long_context=False,
    notes="GQA 32q/8kv",
)
