"""granite-4.0-h-micro [hybrid] — Mamba2 layers with four GQA attention layers.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]

40L d_model=2048; layers 5, 15, 25, 35 are NoPE GQA attention (32 heads over
8 KV heads of 64, softmax scale attention_multiplier 1/64), the other 36
Mamba2 (64 heads of 64, d_state 128, one group, conv 4 with bias, chunk
256).  Every layer is followed by its own SwiGLU MLP of 8192
(shared_intermediate_size; no experts).  Embedding x12, residual adds x0.22,
logits /8; vocab 100352, tied embeddings; 3,191,396,096 parameters.

Not in ``ARCH_IDS``: that list is the frozen dry-run matrix.  It is served
through ``DisaggregatedServer`` (``bench/configs/disagg-granite-4.0-h-micro-xdt``).
"""
import dataclasses

from repro.models.config import HybridConfig, ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab=100352, head_dim=64, rope=False, attn_scale=0.015625,
    rms_eps=1e-5, tie_embeddings=True, subquadratic=True,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    ssm=SSMConfig(d_state=128, version=2, expand=2, conv_width=4, head_dim=64,
                  chunk=256),
    hybrid=HybridConfig(attn_layers=(5, 15, 25, 35)),
)

# two attention layers, with Mamba2 spans before, between and after them
SMOKE = dataclasses.replace(
    CONFIG, n_layers=7, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab=128, head_dim=16, attn_chunk=8, attn_scale=0.125,
    ssm=SSMConfig(d_state=8, version=2, expand=2, conv_width=4, head_dim=16, chunk=8),
    hybrid=HybridConfig(attn_layers=(2, 4)),
)
