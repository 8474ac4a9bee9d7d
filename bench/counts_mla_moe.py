"""Operations and bytes that the served work of the ``mla_moe`` family
(DeepSeek-V3's blocks) needs, computed from the configuration's shapes, the
latent pool's layout and the routed pairs the program counted.  Only needed
work counts: for decode, each row's own context and resident pages (not idle
slots, nor the walk over the longest row's pages), and of the routed
experts only the pairs whose expert this chip holds."""
from __future__ import annotations

from bench.counts import ACT_BYTES, PARAM_BYTES

LANES = 128     # the pool stores each latent token lane-aligned


def _dims(conf):
    return (conf["hidden_size"], conf["num_attention_heads"], conf["q_lora_rank"],
            conf["kv_lora_rank"], conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"])


def latent_width(conf) -> int:
    """Channels of one latent token, ``[c_kv ; k_pe]``: 576 in DeepSeek-V3."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def pool_width(conf) -> int:
    """Channels one latent token takes in the pool: ``latent_width`` rounded
    up to whole lanes (640 in DeepSeek-V3)."""
    return -(-latent_width(conf) // LANES) * LANES


def page_layer_bytes(conf) -> int:
    """Bytes of one page of one layer: the packed codes of ``kv_block``
    latent tokens and one float16 (scale, zero) per channel."""
    eng = conf["engine"]
    w = pool_width(conf)
    return eng["kv_block"] * w * eng["kv_bits"] // 8 + w * 2 * PARAM_BYTES


def page_bytes(conf) -> int:
    """Bytes of one page-table column: one page in every layer."""
    return page_layer_bytes(conf) * conf["num_hidden_layers"]


def mla_params(conf) -> int:
    """Weights one token multiplies in one layer's MLA, absorbed or
    expanded alike: q_down, q_up, kv_down, k_up (absorbed into the query),
    v_up (applied to the latent output) and the output projection."""
    d, h, ql, kvl, dn, dr, dv = _dims(conf)
    return d * ql + ql * h * (dn + dr) + d * (kvl + dr) + kvl * h * dn + kvl * h * dv + h * dv * d


def expert_params(conf) -> int:
    """One SwiGLU expert's weights (gate, up and down)."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def router_params(conf) -> int:
    return conf["hidden_size"] * conf["n_routed_experts"] * conf["expert_chips"]


def dense_mlp_params(conf) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def head_params(conf) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def token_matmul_params(conf) -> int:
    """Weights one decoded token multiplies outside the routed experts, all
    layers: MLA, the dense layers' MLP, the MoE layers' router and shared
    expert, and the (sliced) LM head."""
    n = conf["num_hidden_layers"]
    nd = conf["stage_dense_layers"]
    return (n * mla_params(conf) + nd * dense_mlp_params(conf)
            + (n - nd) * (router_params(conf) + conf["n_shared_experts"] * expert_params(conf))
            + head_params(conf))


def latent_attention_flops(conf, context: int) -> int:
    """Scores over the latent (``kv_lora + qk_rope`` channels) and the
    weighted sum of its ``kv_lora`` value channels, for every head, over
    ``context`` keys, in one layer: 2 x 128 x (576 + 512) = 278,528 FLOPs a
    key in DeepSeek-V3."""
    _, h, _, kvl, _, _, _ = _dims(conf)
    return 2 * h * (latent_width(conf) + kvl) * context


def decode_flops(conf, contexts, pairs: int) -> int:
    """Model FLOPs of one decoded token per entry of ``contexts`` (keys the
    token attends, itself included) and of the ``pairs`` routed (token,
    expert) pairs whose expert this chip holds, summed over the MoE layers."""
    n = conf["num_hidden_layers"]
    total = 2 * token_matmul_params(conf) * len(contexts)
    total += 2 * expert_params(conf) * pairs
    total += n * sum(latent_attention_flops(conf, c) for c in contexts)
    return total


def paged_decode_call(conf, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of one call of the latent paged decode kernel (one
    layer) over rows attending ``contexts`` keys each: a row reads its own
    complete pages in the pool's layout, its bf16 tail, its query (128 x
    576) and writes its output (128 x 512)."""
    _, h, _, kvl, _, _, _ = _dims(conf)
    block = conf["engine"]["kv_block"]
    flops = nbytes = 0
    for c in contexts:
        full, tail = divmod(c, block)
        flops += latent_attention_flops(conf, c)
        nbytes += full * page_layer_bytes(conf)
        nbytes += tail * latent_width(conf) * ACT_BYTES
        nbytes += h * (latent_width(conf) + kvl) * ACT_BYTES
    return flops, nbytes


def expert_step(conf, pairs: int, experts_hit: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the held experts' grouped matmuls over one decode
    step: each pair through gate, up and down; each held expert that has a
    pair read once; each pair's activations in and out of both matmuls in
    bf16."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    flops = 2 * expert_params(conf) * pairs
    nbytes = experts_hit * expert_params(conf) * ACT_BYTES
    nbytes += pairs * (d + 2 * f + f + d) * ACT_BYTES
    return flops, nbytes
