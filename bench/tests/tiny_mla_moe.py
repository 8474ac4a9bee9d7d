"""A tiny configuration with the shapes of ``bench/configs/deepseek-v3.json``
(the same keys, every width cut), for runs of the program, the reference
and the harness on the CPU.  ``kv_lora_rank`` stays a multiple of 128: the
latent kernel slices the value side out of the key tile on lane bounds."""
from __future__ import annotations

import copy

from bench import model


def tiny_config(**over) -> dict:
    conf = copy.deepcopy(model.load_config("deepseek-v3"))
    conf.update(name="deepseek-v3-tiny", hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=64,
                kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=32,
                v_head_dim=32, moe_intermediate_size=64, num_hidden_layers=2,
                vocab_size=512, n_routed_experts=4, expert_chips=4,
                num_experts_per_tok=4, n_group=4, topk_group=2)
    conf["engine"] = dict(conf["engine"], slots=4, max_seq=256, kv_block=64)
    conf.update(over)
    return conf
