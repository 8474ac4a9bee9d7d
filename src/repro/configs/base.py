"""Architecture configuration schema + assigned input-shape registry."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# The four assigned shapes (identical across the 10 LM-family archs).
SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # attention details
    mixer: str = "attn"  # attn | mla | mamba2 | xlstm
    rope: bool = True
    rope_theta: float = 1.0e4
    # YaRN rotary scaling (DeepSeek-V3 ``rope_scaling``); off at factor 1
    yarn_factor: float = 1.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    yarn_original_max_pos: int = 4096
    mrope_sections: tuple | None = None
    qk_norm: bool = False
    attn_bias: bool = False
    n_heads_pad: int = 0  # pad q heads to shard on the TP axis (zero-padded
    # wo rows make the extra heads mathematically inert — Megatron practice)
    parallel_residual: bool = False
    norm: str = "rms"
    act: str = "swiglu"
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: embeddings * sqrt(d)
    rms_plus_one: bool = False  # gemma: (1 + w)
    attn_block_k: int = 512

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    capacity_factor: float = 1.25
    router_score: str = "softmax"
    router_norm_topk: bool = False
    aux_loss_weight: float = 1.0e-2
    # group-limited routing (DeepSeek-V3 ``noaux_tc``): the n_experts are cut
    # into router_groups groups, each scored by the sum of its two best
    # choice scores; the top_k experts come from the router_topk_groups best
    # groups.  router_bias adds a per-expert correction bias to the choice
    # scores only (never to the weights); routed_scaling multiplies the
    # (normalised) weights.
    router_groups: int = 0
    router_topk_groups: int = 0
    router_bias: bool = False
    routed_scaling: float = 1.0
    # expert share (expert parallelism): each layer's n_experts are divided
    # over expert_chips chips; this model holds the n_experts / expert_chips
    # experts of rank expert_rank and computes only their part of the layer
    expert_chips: int = 1
    expert_rank: int = 0

    # MLA (DeepSeek)
    q_lora: int = 0
    kv_lora: int = 0
    qk_nope: int = 0
    qk_rope: int = 0
    v_head_dim: int = 0
    mtp: bool = False

    # SSM / hybrid
    ssm_state: int = 0
    mamba_heads: int = 0
    mamba_d_inner: int = 0
    mamba_groups: int = 1
    mamba_chunk: int = 256
    attn_every: int = 0  # zamba2: shared attention block every k mamba layers
    mlstm_per_slstm: int = 0  # xlstm: super-block = k mLSTM + 1 sLSTM
    xlstm_time_chunk: int = 64  # sqrt-remat chunk for the recurrent time scan
    xlstm_chunkwise: bool = False  # chunkwise-parallel mLSTM (perf iteration)

    # enc-dec (seamless)
    encdec: bool = False
    enc_layers: int = 0
    dec_layers: int = 0
    enc_len: int = 4096  # stub frame-embedding length for decode shapes

    # VLM stub
    vision_stub: bool = False
    n_patches: int = 1024
    patch_grid: tuple = (32, 32)

    # BitDecoding KV cache
    kv_bits: int = 4
    kv_block: int = 128
    kv_gran: str = "channel"

    # training
    optimizer: str = "adamw"
    remat: str = "full"  # none | full
    sharding_profile: str = "fsdp_tp"  # tp | fsdp_tp
    microbatches: int = 8  # grad-accum microbatches for the train shapes

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    @property
    def g_q(self) -> int:
        return self.n_heads // max(1, self.n_kv_heads)

    @property
    def held_experts(self) -> int:
        """Routed experts this model holds: its rank's share of n_experts."""
        return self.n_experts // max(1, self.expert_chips)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the unembedding shards on a
        16-way model axis (Megatron-style padding; logits for padded ids are
        masked to -inf).  SeamlessM4T's 256206 is the motivating case."""
        return -(-self.vocab // 256) * 256


_REGISTRY = [
    "qwen3_moe_235b_a22b",
    "deepseek_v3_671b",
    "command_r_35b",
    "gemma_7b",
    "llama3_8b",
    "starcoder2_3b",
    "xlstm_1_3b",
    "seamless_m4t_medium",
    "zamba2_7b",
    "qwen2_vl_7b",
    "llama2_7b",  # the paper's own MHA eval model
]


def _mod_name(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def list_configs() -> list[str]:
    return [importlib.import_module(f"repro.configs.{n}").CONFIG.name for n in _REGISTRY]


def get_config(name: str) -> ArchConfig:
    mod_name = _mod_name(name)
    if mod_name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; available: {_REGISTRY}")
    return importlib.import_module(f"repro.configs.{mod_name}").CONFIG


def smoke_config(name: str) -> ArchConfig:
    """A reduced same-family config for CPU smoke tests."""
    return importlib.import_module(f"repro.configs.{_mod_name(name)}").SMOKE
