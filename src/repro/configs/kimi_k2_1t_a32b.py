"""kimi-k2-1t-a32b [moe]: 61L d=7168, MLA 64H (q rank 1536, kv rank 512,
qk 128+64, v 128), 1 dense layer (d_ff=18432) then MoE layers of 384
experts (width 2048, 8 per token, 1 shared; sigmoid routing, normalized
gates x 2.827), vocab=163840, untied [hf:moonshotai/Kimi-K2-Instruct].

Departure: the source stretches RoPE with YaRN (factor 32); the program
rotates at the base theta and scales attention by 1/sqrt(192).

Optimizer is Adafactor: Adam's 2d f32 states for ~1T params cannot fit
512 x 16 GB HBM; factored second moments do (DESIGN.md §4).
"""
from repro.models.config import LayerSpec, ModelConfig, MoECfg

ID = "kimi-k2-1t-a32b"


def full_config() -> ModelConfig:
    return ModelConfig(
        name=ID, n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64,
        d_ff=18432, vocab=163840, pattern=(LayerSpec("mla", "moe"),),
        n_dense_layers=1, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, norm_eps=1e-6,
        moe=MoECfg(n_experts=384, top_k=8, d_ff_expert=2048,
                   capacity_factor=None, n_shared_experts=1,
                   scoring="sigmoid", routed_scale=2.827),
        tie_embeddings=False, rope_theta=50000.0, cut_layers=1,
        family="moe", optimizer="adafactor")


def smoke_config() -> ModelConfig:
    full = full_config()
    return full.replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        vocab=257, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8,
        qk_rope_dim=8, v_head_dim=8,
        moe=MoECfg(**{**full.moe.__dict__, "n_experts": 8, "top_k": 2,
                      "d_ff_expert": 32}),
        param_dtype="float32", compute_dtype="float32",
        q_chunk=16, kv_chunk=16)
