"""AI21-Jamba2-Mini (52B total, 12B active): the Jamba Mini block —
Mamba-1 and attention 7:1, 16 experts top-2 on every other layer.
Period of 8 with attention at offset 4 (``attn_layer_period`` 8,
``attn_layer_offset`` 4) and experts on odd layers
(``expert_layer_period`` 2, ``expert_layer_offset`` 1); 32 layers = 4
periods. Attention has no positional encoding; the Mamba mixer normalizes
dt, B and C before dt_proj and the scan; the router keeps the top-2
softmax probabilities without renormalizing them. Served with f32
matmuls at f32 accuracy: the choice of the top 2 of 16 experts is
discrete, and one bf16 pass per matmul re-routes tokens whose second and
third experts nearly tie.
[hf: ai21labs/AI21-Jamba2-Mini config.json; arXiv:2403.19887]"""
from .base import ArchConfig

_PERIOD = (
    "mamba+dense", "mamba+moe", "mamba+dense", "mamba+moe",
    "attn+dense", "mamba+moe", "mamba+dense", "mamba+moe",
)

CONFIG = ArchConfig(
    name="jamba2-mini", family="hybrid",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=14336, vocab_size=65536,
    mlp_variant="swiglu", norm="rmsnorm",
    nope=True,
    n_experts=16, top_k=2, router_renorm=False,
    pattern=_PERIOD,
    ssm_d_state=16, ssm_d_conv=4, ssm_expand=2, ssm_dt_rank=256,
    ssm_bcdt_norm=True,
    matmul_precision="highest",
    source="https://huggingface.co/ai21labs/AI21-Jamba2-Mini",
)
