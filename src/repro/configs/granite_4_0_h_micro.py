"""granite-4.0-h-micro: Mamba2 + NoPE GQA hybrid with muP multipliers
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

40 layers in a period of ten: attention at layers 5, 15, 25 and 35, Mamba2
elsewhere; every layer's mixer is followed by a SwiGLU MLP of width 8192.
``PERIOD`` is ``layer_types[0:10]``, one whole period (9 Mamba2 + 1
attention), the depth one chip holds for federated adapter training.
"""
from repro.config import ModelConfig

_PERIOD_TYPES = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="pattern",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8,
    d_ff=8192, vocab_size=100_352, head_dim=64,
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_conv_width=4,
    ssm_groups=1, ssm_chunk=256,
    layer_types=_PERIOD_TYPES * 4,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=0.015625,
    norm_eps=1e-5, tie_embeddings=True,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)

PERIOD = ModelConfig(
    **{**CONFIG.__dict__, "name": "granite-4.0-h-micro-period",
       "num_layers": 10, "layer_types": _PERIOD_TYPES},
)

SMOKE = ModelConfig(
    name="granite-h-smoke", family="pattern",
    num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
    d_ff=96, vocab_size=512, head_dim=16,
    ssm_state=16, ssm_expand=2, ssm_head_dim=16, ssm_conv_width=4,
    ssm_groups=1, ssm_chunk=16,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=1.0 / 16,
    norm_eps=1e-5, tie_embeddings=True,
    source="reduced granite-4.0-h family",
)
