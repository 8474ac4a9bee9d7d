"""The operation and byte counts behind the rooflines and MFUs count
needed work only, at the pool's own layout."""
import pytest

from bench import counts, model


@pytest.mark.parametrize("bits,gran", [(4, "channel"), (2, "channel"), (4, "tensor")])
def test_page_bytes_equal_the_engine_pool(bits, gran):
    from repro.serve.engine import ServeEngine

    conf = model.load_config("qwen2-7b")
    conf["engine"] = dict(conf["engine"], kv_bits=bits, kv_gran=gran)
    eng = ServeEngine(model.build_program(conf), None, slots=1,
                      max_seq=2 * conf["engine"]["kv_block"], n_pages=3)
    assert counts.page_bytes(conf) == eng.kv_page_bytes


def test_qwen_page_bytes_by_hand():
    # 4 KV heads x 128 tokens x 128 dims at 4 bits, K and V, plus float16
    # (scale, zero) per K channel and per V token, in 14 layers
    conf = model.load_config("qwen2-7b")
    assert counts.page_bytes(conf) == 14 * (2 * 4 * 128 * 128 // 2 + 4 * 128 * 4 + 4 * 128 * 4)


def test_decode_bytes_count_each_rows_own_pages():
    conf = model.load_config("qwen2-7b")
    pb, hq, hkv, hd = counts.page_layer_bytes(conf), 28, 4, 128
    flops, nbytes = counts.paged_decode_call(conf, [1000, 129])
    own = (1000 // 128 + 129 // 128) * pb
    tails = (1000 % 128 + 129 % 128) * 2 * hkv * hd * 2
    q_out = 2 * 2 * hq * hd * 2
    assert nbytes == own + tails + q_out
    walk = 2 * (1000 // 128) * pb  # every row over the longest row's pages
    assert nbytes < walk
    assert flops == 4 * hq * hd * (1000 + 129)


def test_decode_flops_by_hand():
    conf = model.load_config("qwen2-7b")
    per_layer = 233_046_016  # q, k, v, o and the SwiGLU MLP at Qwen2-7B widths
    assert counts.layer_matmul_params(conf) == per_layer
    head = 3584 * 152064
    assert counts.decode_flops(conf, [10]) == 2 * (14 * per_layer + head) + 4 * 28 * 128 * 10 * 14
