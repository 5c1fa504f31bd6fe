"""Unified model configuration for all assigned architectures."""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int                 # the router's outputs (all experts)
    top_k: int
    d_ff_expert: int
    capacity_factor: float | None = 1.25  # None: dropless over the held
                                          # experts (models/moe.moe_held)
    n_shared_experts: int = 0      # shared experts, one gated MLP
    router_dtype: str = "float32"
    scoring: str = "softmax"       # softmax | sigmoid (DeepSeek-V3)
    routed_scale: float = 1.0      # routed_scaling_factor
    n_held: int = 0                # experts this chip holds; 0 => all
    expert_offset: int = 0         # global index of the first one held

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer = mixer + ffn."""
    mixer: str = "global_attn"     # global_attn|local_attn|mla|rg_lru|
                                   # mlstm|slstm
    ffn: str = "dense"             # dense|moe|none


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # --- layer pattern: repeated cyclically to n_layers ---
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    head_dim: int = 0              # 0 => d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = True
    norm: str = "rmsnorm"          # rmsnorm|layernorm
    post_norm: bool = False        # gemma2-style post-block norms
    activation: str = "silu"
    gated_mlp: bool = True
    rope_kind: str = "rope"        # rope|mrope|none
    rope_theta: float = 10000.0
    mrope_sections: tuple[int, ...] = ()
    attn_softcap: float | None = None
    final_softcap: float | None = None
    attn_scale: float | None = None
    window: int = 4096             # local-attention window
    # --- multi-head latent attention (mixer "mla", DeepSeek-V2/V3) ---
    q_lora_rank: int = 0           # 0 => q = x @ wq (no low-rank path)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    norm_eps: float | None = None  # None => each norm's own default
    n_dense_layers: int = 0        # leading layers whose ffn is dense
    moe: MoECfg | None = None
    # --- enc-dec (seamless-m4t) ---
    enc_dec: bool = False
    n_enc_layers: int = 0
    # --- recurrent (xlstm / recurrentgemma) ---
    lru_width: int = 0             # 0 => d_model
    conv_width: int = 4
    # --- modality frontend stub ---
    frontend: str | None = None    # None|"audio"|"vision"
    # --- SFL split ---
    cut_layers: int = 2            # client-side depth (paper's cut layer)
    aux_layers: int = 0            # extra transformer blocks in the aux head
    # --- numerics ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # --- performance knobs (hillclimbing surface) ---
    attn_impl: str = "blocked"     # naive|blocked
    q_chunk: int = 1024
    kv_chunk: int = 1024
    causal_skip: bool = False      # static causal block skipping (perf opt)
    mlstm_chunk: int = 0           # 0 = sequential scan; >0 = chunkwise
    seq_sharding: bool = False     # shard attention q/residual seq over model
    attn_p_dtype: str = "float32"  # dtype of the softmax p matrix fed to p@v
    remat: bool = True             # activation checkpointing on scan segments
    remat_policy: str = "nothing"  # nothing|save_gathers (keep FSDP-gathered
                                   # MoE weights across the bwd replay)
    scan_layers: bool = True
    forward_impl: str = "kernel"   # kernel | kernel_interpret: the
                                   # backend of the ZO client's dual-probe
                                   # kernels — "kernel" picks it from the
                                   # platform (compiled on the TPU, the
                                   # bit-equivalent jnp emulation
                                   # elsewhere); "kernel_interpret" runs
                                   # the Pallas kernels in interpret mode
    attn_probe: str = "weights"    # weights | scores:
                                   # "weights" perturbs wq/wk/wv/wo and
                                   # runs both streams' own K/V through
                                   # one fused flash pass; "scores" keeps
                                   # K/V clean+shared between streams and
                                   # perturbs the pre-softmax scores with
                                   # the hash field instead (wk/wv leave
                                   # the seed stream — see
                                   # ops.attn_kv_seed_pred)
    optimizer: str = "adamw"       # adamw|adafactor|sgdm (server side)
    # assigned-shape bookkeeping
    family: str = "dense"          # dense|moe|audio|ssm|hybrid|vlm
    subquadratic: bool = False     # eligible for long_500k

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return ((self.vocab + 255) // 256) * 256

    def jnp_param_dtype(self):
        return jnp.dtype(self.param_dtype)

    def jnp_compute_dtype(self):
        return jnp.dtype(self.compute_dtype)

    def layer_specs(self) -> tuple[LayerSpec, ...]:
        reps = (self.n_layers + len(self.pattern) - 1) // len(self.pattern)
        specs = (self.pattern * reps)[: self.n_layers]
        return tuple(dataclasses.replace(s, ffn="dense")
                     if i < self.n_dense_layers else s
                     for i, s in enumerate(specs))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
