"""trinity-mini — AFMoE 26B-A3B: 2 dense + 30 MoE layers (128 routed top-8
experts, sigmoid routing, 1 shared), sliding (2048) and full attention
3:1 [hf:arcee-ai/Trinity-Mini; hf]."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="trinity-mini",
    family="moe",
    n_layers=32,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=6144,
    vocab_size=200192,
    qk_norm=True,
    layer_windows=(2048, 2048, 2048, 0),
    layer_rope=(True, True, True, False),
    attn_out_gate=True,
    sandwich_norm=True,
    embed_scale=True,
    rope_theta=1e4,
    norm_eps=1e-5,
    n_experts=128,
    n_shared_experts=1,
    top_k=8,
    moe_d_ff=1024,
    n_dense_layers=2,
    router_score="sigmoid",
    router_bias=True,
    route_scale=2.826,
    shared_expert_gate=False,
    source="[hf:arcee-ai/Trinity-Mini; hf]",
)
