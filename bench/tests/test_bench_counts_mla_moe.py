"""The operation and byte counts of the ``mla_moe`` family count needed
work only, at the latent pool's own layout and DeepSeek-V3's widths."""
from bench import counts_mla_moe as counts
from bench import model

V3 = model.load_config("deepseek-v3")


def test_page_bytes_equal_the_engine_pool():
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(model.build_program(V3), None, slots=1,
                      max_seq=2 * V3["engine"]["kv_block"], n_pages=3)
    assert counts.page_bytes(V3) == eng.kv_page_bytes


def test_latent_page_bytes_by_hand():
    # 576 latent channels stored in 640 lanes: 128 tokens x 640 at 4 bits,
    # plus a float16 (scale, zero) per channel, in 8 layers
    assert counts.pool_width(V3) == 640
    assert counts.page_bytes(V3) == 8 * (128 * 640 // 2 + 640 * 4) == 348_160


def test_decode_flops_by_hand():
    mla = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 2 * 512 * 128 * 128 + 128 * 128 * 7168
    assert counts.mla_params(V3) == mla == 187_105_280
    expert = 3 * 7168 * 2048
    dense = 3 * 7168 * 18432
    router = 7168 * 256
    head = 7168 * 16384
    per_token = 8 * mla + dense + 7 * (router + expert) + head
    assert counts.latent_attention_flops(V3, 1) == 278_528
    assert counts.decode_flops(V3, [100, 7], 11) == (
        2 * per_token * 2 + 2 * expert * 11 + 8 * 278_528 * 107)


def test_latent_call_counts_each_rows_own_pages():
    flops, nbytes = counts.paged_decode_call(V3, [1000, 129])
    page = 128 * 640 // 2 + 640 * 4
    own = (1000 // 128 + 129 // 128) * page
    tails = (1000 % 128 + 129 % 128) * 576 * 2
    q_out = 2 * 128 * (576 + 512) * 2
    assert nbytes == own + tails + q_out
    assert flops == 278_528 * 1129
    # 128 heads over one latent stream: far beyond the v5e's ridge
    assert flops / nbytes > 197e12 / 819e9


def test_expert_step_reads_each_hit_expert_once():
    flops, nbytes = counts.expert_step(V3, pairs=8, experts_hit=5)
    expert = 3 * 7168 * 2048
    assert flops == 2 * expert * 8
    assert nbytes == 5 * expert * 2 + 8 * (7168 + 3 * 2048 + 7168) * 2
