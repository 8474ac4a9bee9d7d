"""The program under test for a dense decoder with grouped-query attention,
RMSNorm and a SwiGLU MLP (``"family": "dense_gqa"``): the program's
``ArchConfig`` and model for a configuration file."""
from __future__ import annotations


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for ``conf``."""
    from repro.configs.base import ArchConfig

    if conf["hidden_act"] != "silu" or conf["norm_type"] != "rms_norm":
        raise ValueError("dense_gqa serves SwiGLU blocks with RMSNorm")
    if float(conf["rms_norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    eng = conf["engine"]
    # the program gives the MLP a bias exactly when it gives q/k/v one; a
    # published MLP without bias runs with those weights held at zero
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]), act="swiglu", norm="rms",
        attn_bias=bool(conf["qkv_bias"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        kv_bits=eng["kv_bits"], kv_block=eng["kv_block"],
        kv_gran=eng["kv_gran"], remat="none",
    )


def build_program(conf: dict):
    """The program's model object for ``conf``."""
    from repro.models.zoo import build_model

    return build_model(arch_config(conf))
