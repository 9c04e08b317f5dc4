"""Arcee Trinity-Mini (AFMoE, 26B-A3B)
[hf:arcee-ai/Trinity-Mini config.json].

32 layers: two leading layers with a dense SwiGLU MLP, then 30 MoE layers
(128 sigmoid-routed experts, top-8, gates renormalized and scaled by
2.826, a selection-only expert bias, one shared expert).  Three sliding-window
(2048) layers to one global layer: layers 3, 7, ..., 31 are global.
Sliding layers rotate q and k (RoPE, theta 10,000); global layers use no
positional encoding.  qk-norm, a sigmoid gate on the attention output,
sandwich norms (each sublayer's output normed before the residual) and the
muP embedding scale sqrt(d).

Pattern: lead (S, S), period (S, F, S, S) x 7 = layers 2..29, tail (S, F).
"""
from repro.configs.base import ATTN, LOCAL_ATTN, ModelConfig, MoEConfig

_S, _F = LOCAL_ATTN, ATTN

CONFIG = ModelConfig(
    name="trinity-mini",
    family="moe",
    num_layers=32,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=6144,                  # the leading dense layers' MLP
    vocab_size=200192,
    qk_norm=True,
    rope_theta=10000.0,
    attention_window=2048,
    attn_out_gate=True,
    rope_local_only=True,
    norm_eps=1e-5,
    scale_embedding=True,       # muP: embeddings times sqrt(d_model)
    sandwich_norm=True,
    lead=(_S, _S),
    period=(_S, _F, _S, _S),
    tail=(_S, _F),
    moe=MoEConfig(
        num_experts=128,
        num_experts_per_tok=8,
        expert_d_ff=1024,
        num_shared_experts=1,
        shared_d_ff=1024,
        score_func="sigmoid",
        selection_bias=True,
        route_scale=2.826,
    ),
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="trinity-smoke",
        family="moe",
        num_layers=6,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=96,
        vocab_size=512,
        qk_norm=True,
        rope_theta=10000.0,
        attention_window=8,
        attn_out_gate=True,
        rope_local_only=True,
        norm_eps=1e-5,
        scale_embedding=True,
        sandwich_norm=True,
        lead=(_S, _S),
        period=(_S, _F, _S, _S),
        moe=MoEConfig(
            num_experts=8, num_experts_per_tok=2, expert_d_ff=32,
            num_shared_experts=1, shared_d_ff=32, score_func="sigmoid",
            selection_bias=True, route_scale=2.826),
    )
