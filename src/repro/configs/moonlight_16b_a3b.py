"""moonlight-16b-a3b [moe]: 27L d=2048, MLA 16H (kv rank 512, qk 128+64,
v 128), 1 dense layer (d_ff=11264) then 26 MoE layers of 64 experts
(width 1408, 6 per token, 2 shared; sigmoid routing with bias-free
selection, normalized gates x 2.446), vocab=163840, untied
[hf:moonshotai/Moonlight-16B-A3B; model_type deepseek_v3].

``full_config`` is the published model.  ``chip_share`` is what one chip
holds when each layer is spread over 8 chips by expert parallelism and
the vocabulary is split 8 ways: experts 0-7 of every MoE layer, rows
0-20479 of the vocabulary, and the leading dense layer plus the next 5
layers (the layers left out lie on further chips, as pipeline stages).
Every width is the published one.  The split is 2 client blocks (the
dense layer and MoE layer 1) with a 1-block aux head; the server holds
layers 2-5, the final norm and the unembedding slice.
"""
from repro.models.config import LayerSpec, ModelConfig, MoECfg

ID = "moonlight-16b-a3b"


def full_config() -> ModelConfig:
    return ModelConfig(
        name=ID, n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=11264, vocab=163840, pattern=(LayerSpec("mla", "moe"),),
        n_dense_layers=1, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, rope_theta=50000.0, norm_eps=1e-5,
        moe=MoECfg(n_experts=64, top_k=6, d_ff_expert=1408,
                   capacity_factor=None, n_shared_experts=2,
                   scoring="sigmoid", routed_scale=2.446),
        tie_embeddings=False, cut_layers=2, aux_layers=1,
        q_chunk=1024, kv_chunk=1024, causal_skip=True, family="moe",
        optimizer="adamw")


def chip_share() -> ModelConfig:
    full = full_config()
    return full.replace(
        n_layers=6, vocab=full.vocab // 8,
        moe=MoECfg(**{**full.moe.__dict__, "n_held": 8,
                      "expert_offset": 0}))


def smoke_config() -> ModelConfig:
    full = full_config()
    return full.replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
        vocab=257, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=8, cut_layers=2, aux_layers=1,
        moe=MoECfg(**{**full.moe.__dict__, "n_experts": 8, "top_k": 2,
                      "d_ff_expert": 32, "n_held": 4}),
        param_dtype="float32", compute_dtype="float32",
        q_chunk=16, kv_chunk=16)
