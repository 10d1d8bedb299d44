"""trinity-mini — [moe] 32L d_model=2048 32H (GQA kv=4) head_dim=128
d_ff=6144 vocab=200192, MoE 128e top-8 (expert hidden 1024), 1 shared
expert; sliding-window attention (2048) with global attention at every 4th
layer: 24 sliding + 8 full. [hf:arcee-ai/Trinity-Mini config.json]

The MoE sublayer is the published AFMoE one (DeepSeek-V3's router and
combine under Trinity's key names): sigmoid scores per expert
(``score_func``), the top 8 chosen on the scores plus a per-layer expert
bias that never enters the weights (aux-loss-free balancing; ``n_group`` 1
limits nothing), the chosen scores renormalised (``route_norm``) and scaled
by ``route_scale`` 2.826, plus one shared expert of width 1024 added with no
gate. No token is dropped (``moe_drop_tokens`` False): the model's
``gather`` dispatch sorts the assignments by expert and runs grouped GEMMs
over exactly the active rows.

Notes: the published stack starts with 2 dense layers
(``num_dense_layers`` 2); this config's stack has an MoE sublayer in every
layer, as the model stack has no leading dense layers. The attention and
MoE fields are as published, which is what the ``kernel_variants``
attention and moe sites read.
"""

from repro.models import ModelConfig

FULL = ModelConfig(
    name="trinity-mini",
    family="moe",
    n_layers=32,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=6144,
    vocab_size=200192,
    n_experts=128,
    top_k=8,
    n_shared_experts=1,
    moe_d_ff=1024,
    shared_d_ff=1024,
    moe_norm_topk=True,
    moe_score_func="sigmoid",
    moe_route_scale=2.826,
    moe_shared_gate=False,
    moe_drop_tokens=False,
    sliding_window=2048,
    global_attn_every_n_layers=4,
    rope_theta=10000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
)

SMOKE = FULL.replace(
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=1,
    head_dim=16,
    d_ff=96,
    vocab_size=512,
    n_experts=8,
    top_k=2,
    moe_d_ff=32,
    shared_d_ff=32,
    sliding_window=8,
    dtype="float32",
    param_dtype="float32",
)
