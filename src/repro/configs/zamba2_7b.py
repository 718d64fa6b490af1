"""zamba2-7b [hybrid] — Zyphra/Zamba2-7B-Instruct as published.

Source: https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json
(the layer equations of ``transformers``' ``modeling_zamba2``).  81 Mamba2
layers (112 heads of 64, state 64, 2 B/C groups, conv 4 with bias, chunks
of 256); before each of the 13 ``hybrid_layer_ids`` one of two shared
attention blocks is applied in turn, on ``concat(hidden, embedding)``, with
a rank-128 LoRA on its MLP and a ``linear`` of its own per application.
Full attention over the published 4096 positions.

``CUT`` is one chip's share of a five-stage pipeline: layers 0-17, three
applications (blocks 0, 1, 0), every width as published.
"""
from repro.configs.base import ModelConfig

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    d_ff=14336,
    vocab_size=32000,
    head_dim=224,
    tie_embeddings=True,
    rope_theta=1e4,
    ssm_state=64,
    ssm_chunk=256,
    ssm_groups=2,
    ssm_dt_min=0.001,
    expand=2,
    conv_kernel=4,
    block_pattern="".join("h" if i in HYBRID_LAYER_IDS else "m"
                          for i in range(81)),
    n_shared_blocks=2,
    adapter_rank=128,
    attn_in=7168,
    attn_scale=(224 / 2) ** -0.5,
)

CUT = CONFIG.first_layers(18)
