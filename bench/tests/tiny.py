"""Tiny configurations and mixes with the shapes of the benchmark's own,
for runs of the harness on the CPU."""
from __future__ import annotations

import copy
import types

from bench import model, traffic


def tiny_config(name: str) -> dict:
    conf = copy.deepcopy(model.load_config(name))
    conf.update(name=f"{name}-tiny", hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                num_hidden_layers=2, vocab_size=512)
    conf["engine"] = dict(conf["engine"], slots=4, max_seq=256, kv_block=64)
    return conf


def tiny_mix(name: str) -> dict:
    mix = copy.deepcopy(traffic.load_mix(name))
    mix.update(specs=16, clients=4, warm_decode_steps=12, warm_admissions=4,
               prompt_tokens=dict(mix["prompt_tokens"], median=40, min=20, max=70),
               output_tokens=dict(mix["output_tokens"], min=10, max=60))
    return mix


def args(seed=7, seconds=2.0, trace=0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
