"""DeepSeek-V3 671B [arXiv:2412.19437; huggingface.co/deepseek-ai/DeepSeek-V3
config.json].

- MLA on all 61 layers: q_lora 1536, kv_lora 512, 128 heads of
  qk_nope 128 + qk_rope 64, v_head_dim 128; the quantized latent cache
  ``[c_kv ; k_rope]`` decodes in ``paged_bitdecode``'s shared_kv mode.
- YaRN rotary scaling on the 64 rope channels: factor 40, beta_fast 32,
  beta_slow 1, mscale = mscale_all_dim = 1, original context 4096; the
  softmax scale carries mscale^2 = (1 + 0.1 ln 40)^2.
- The first 3 layers dense (d_ff 18432), then MoE: 256 routed experts of
  width 2048 and 1 shared expert.  The ``noaux_tc`` router: sigmoid scores,
  the choice made on score + e_score_correction_bias over 8 groups of 32
  (each group scored by its two best), 4 groups kept, top 8 experts; the
  weights are the unbiased scores, normalised, times 2.5.
- The multi-token-prediction module (one extra layer) is a training-time
  head here (``mtp``); serving does not use it.

Adafactor + ZeRO-3: AdamW fp32 states for 671B params exceed 256x v5e HBM
(DESIGN.md §7)."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v3-671b", family="moe", mixer="mla",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=18432, vocab=129280,
    rope_theta=10000.0, act="swiglu", norm="rms",
    yarn_factor=40.0, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=1.0, yarn_mscale_all_dim=1.0, yarn_original_max_pos=4096,
    n_experts=256, top_k=8, d_expert=2048, n_shared_experts=1,
    first_dense_layers=3, router_score="sigmoid", router_norm_topk=True,
    router_groups=8, router_topk_groups=4, router_bias=True,
    routed_scaling=2.5,
    q_lora=1536, kv_lora=512, qk_nope=128, qk_rope=64, v_head_dim=128,
    mtp=True,
    optimizer="adafactor", sharding_profile="fsdp_tp",
)

SMOKE = CONFIG.with_(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=256, vocab=512, n_experts=8, top_k=2, d_expert=64,
    router_groups=4, router_topk_groups=2,
    first_dense_layers=1, q_lora=64, kv_lora=128, qk_nope=32, qk_rope=32,
    v_head_dim=32, kv_block=64, attn_block_k=64, remat="none",
)
