"""The program under test for a decoder with multi-head latent attention
and routed experts, DeepSeek-V3's blocks (``"family": "mla_moe"``): the
program's ``ArchConfig`` and model for a configuration file.  The model is
this chip's share of the stated deployment: ``stage_dense_layers`` of the
leading dense layers, then MoE layers up to ``num_hidden_layers``, each
holding the ``n_routed_experts`` experts of rank ``expert_rank`` out of the
``n_routed_experts * expert_chips`` its router scores."""
from __future__ import annotations


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for ``conf``."""
    from repro.configs.base import ArchConfig

    if conf["hidden_act"] != "silu" or float(conf["rms_norm_eps"]) != 1e-6:
        raise ValueError("mla_moe serves SwiGLU blocks with RMSNorm at epsilon 1e-6")
    if conf["scoring_func"] != "sigmoid" or conf["topk_method"] != "noaux_tc":
        raise ValueError("mla_moe routes with sigmoid scores and noaux_tc")
    if conf["moe_layer_freq"] != 1 or conf["attention_bias"]:
        raise ValueError("mla_moe has an MoE block in every layer after the dense ones "
                         "and no attention bias")
    yarn = conf["rope_scaling"]
    if yarn["type"] != "yarn":
        raise ValueError("mla_moe's rotary scaling is YaRN")
    eng = conf["engine"]
    return ArchConfig(
        name=conf["name"], family="moe", mixer="mla",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), act="swiglu", norm="rms",
        yarn_factor=float(yarn["factor"]), yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]), yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        yarn_original_max_pos=int(yarn["original_max_position_embeddings"]),
        q_lora=conf["q_lora_rank"], kv_lora=conf["kv_lora_rank"],
        qk_nope=conf["qk_nope_head_dim"], qk_rope=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_experts=conf["n_routed_experts"] * conf["expert_chips"],
        expert_chips=conf["expert_chips"], expert_rank=conf["expert_rank"],
        top_k=conf["num_experts_per_tok"], d_expert=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        first_dense_layers=conf["stage_dense_layers"],
        router_score="sigmoid", router_norm_topk=bool(conf["norm_topk_prob"]),
        router_groups=conf["n_group"], router_topk_groups=conf["topk_group"],
        router_bias=True, routed_scaling=float(conf["routed_scaling_factor"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]), mtp=False,
        kv_bits=eng["kv_bits"], kv_block=eng["kv_block"],
        kv_gran=eng["kv_gran"], remat="none",
    )


def build_program(conf: dict):
    """The program's model object for ``conf``."""
    from repro.models.zoo import build_model

    return build_model(arch_config(conf))
