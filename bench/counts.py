"""Operations and bytes that the served work needs, computed from the
configuration's shapes and the page pool's layout.  Only needed work
counts: for decode, each row's own context and resident pages (not idle
slots, nor the walk over the longest row's pages)."""
from __future__ import annotations

PARAM_BYTES = 2      # float16 scale and zero
ACT_BYTES = 2        # bfloat16 activations, residual and cache inputs


def _dims(conf):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"])


def layer_matmul_params(conf) -> int:
    """Weights one token multiplies in one layer."""
    d, hq, hkv, hd, ff, _, _ = _dims(conf)
    # q, k, v, o, and the SwiGLU MLP's gate, up and down projections
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def head_params(conf) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def page_layer_bytes(conf) -> int:
    """Bytes of one page of one layer: packed K and V codes, K's scale and
    zero (per channel, or per token with ``kv_gran="tensor"``) and V's (per
    token)."""
    _, _, hkv, hd, _, _, _ = _dims(conf)
    eng = conf["engine"]
    block, bits = eng["kv_block"], eng["kv_bits"]
    codes = 2 * hkv * block * hd * bits // 8
    k_params = hkv * (hd if eng["kv_gran"] == "channel" else block) * 2 * PARAM_BYTES
    v_params = hkv * block * 2 * PARAM_BYTES
    return codes + k_params + v_params


def page_bytes(conf) -> int:
    """Bytes of one page-table column: one page in every layer."""
    return page_layer_bytes(conf) * conf["num_hidden_layers"]


def attention_flops(conf, queries_keys: int) -> int:
    """QK and PV over ``queries_keys`` (query, key) pairs, all layers."""
    _, hq, _, hd, _, _, n = _dims(conf)
    return 4 * hq * hd * queries_keys * n


def decode_flops(conf, contexts) -> int:
    """Model FLOPs of one decoded token per entry of ``contexts`` (keys the
    token attends, itself included): every matmul, the LM head, attention."""
    n = conf["num_hidden_layers"]
    total = 0
    for c in contexts:
        total += 2 * (layer_matmul_params(conf) * n + head_params(conf))
        total += attention_flops(conf, c)
    return total


def paged_decode_call(conf, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of one call of the paged decode kernel (one layer) over
    rows attending ``contexts`` keys each: a row reads its own complete
    pages, its bf16 residual tail, its query and writes its output."""
    _, hq, hkv, hd, _, _, _ = _dims(conf)
    block = conf["engine"]["kv_block"]
    flops = nbytes = 0
    for c in contexts:
        full, tail = divmod(c, block)
        flops += 4 * hq * hd * c
        nbytes += full * page_layer_bytes(conf)
        nbytes += tail * 2 * hkv * hd * ACT_BYTES
        nbytes += 2 * hq * hd * ACT_BYTES
    return flops, nbytes
