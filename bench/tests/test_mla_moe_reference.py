"""The program's DeepSeek-V3 blocks against the plain reference
(``bench/reference/mla_moe.py``) and literal transcriptions of the published
formulas, on seeded random weights at V3-shaped tiny widths on the CPU:
prefill and paged latent decode through ``ServeEngine``, the sum of every
rank's expert share, the ``noaux_tc`` router, YaRN, and the share's
droplessness."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model
from bench.reference import mla_moe as ref
from bench.tests.tiny_mla_moe import tiny_config
from repro.models import layers, mla, moe

V3 = model.load_config("deepseek-v3")


# ------------------------------------------------------- served logits


@pytest.fixture(scope="module")
def served():
    """One request served greedily through the engine (bucketed prefill,
    page splice, paged latent decode and flush), with every decode step's
    logits row, beside the reference's logits over the whole sequence."""
    from repro.serve.engine import Request, ServeEngine

    conf = tiny_config()
    prog = model.build_program(conf)
    w = ref.init_weights(conf, jax.random.PRNGKey(3))
    eng = ServeEngine(prog, w, slots=4, max_seq=256, min_bucket=16)
    rows, step = [], eng._step

    def keep_logits(p, s, t):
        logits, state = step(p, s, t)
        rows.append(np.asarray(logits[0, 0], np.float32))
        return logits, state

    eng._step = keep_logits
    prompt = np.random.default_rng(0).integers(0, conf["vocab_size"], 20).astype(np.int32)
    req = Request(uid=0, prompt=prompt, max_new_tokens=150)
    eng.submit(req)
    eng.run()
    seq = np.concatenate([prompt, np.asarray(req.out_tokens[:-1], np.int32)])
    toks = np.zeros(256, np.int32)
    toks[:len(seq)] = seq
    v = conf["vocab_size"]
    every = jnp.asarray(np.broadcast_to(np.arange(v)[:, None], (v, 256)).astype(np.int32))
    full = {}
    for fp8 in (False, True):
        _, _, at = ref.scores(conf, w, jnp.asarray(toks), len(prompt), every, fp8=fp8,
                              q_chunk=64, v_chunk=64)
        full[fp8] = np.asarray(at).T
    # decode step i feeds position len(prompt) + i: the reference's row there
    n = len(req.out_tokens) - 1
    p0 = len(prompt)
    return np.stack(rows[:n]), full[False][p0:p0 + n], full[True][p0:p0 + n], p0


def test_prefill_and_paged_decode_match_the_reference(served):
    got, want, _, p0 = served
    block = tiny_config()["engine"]["kv_block"]
    err = np.abs(got - want)
    raw = err[: block - p0]          # the latent still in the bf16 residual
    quant = err[block - p0:]         # complete blocks read as 4-bit codes
    assert want.std() > 0.5
    # bf16 activations and weights against float32 over two layers: about
    # 1 % of the logits' spread (measured 0.012-0.015 mean, 0.24 largest)
    assert raw.mean() < 0.03 and raw.max() < 0.5
    # 4-bit codes of a bf16 latent against codes of a float32 one: about
    # 1-2 % of the codes land one step apart (a rounding boundary between
    # the two), half the size of the quantization's own effect (0.13);
    # measured 0.09-0.10 mean
    assert quant.mean() < 0.15


def test_fp8_reference_falls_outside_the_residual_tolerance(served):
    """The tolerance on the unquantized stretch is tight enough that the
    reference with fp8 operands (one step below bf16) fails it."""
    _, want, want8, p0 = served
    block = tiny_config()["engine"]["kv_block"]
    assert np.abs(want8 - want)[: block - p0].mean() > 0.03


# ------------------------------------------------------- the expert share


def _share_config(rank=0, chips=32, held=2):
    conf = tiny_config(n_routed_experts=held, expert_chips=chips, expert_rank=rank,
                       n_group=8, topk_group=4, num_experts_per_tok=8)
    return model.build_program(conf).cfg, conf


def test_every_ranks_share_adds_up_to_the_whole_layer():
    """32 ranks of 2 experts each: their parts, with the shared expert
    counted once, give the uncut layer of 64 experts."""
    cfg, conf = _share_config()
    whole_conf = dict(conf, n_routed_experts=64, expert_chips=1)
    w = ref.init_weights(whole_conf, jax.random.PRNGKey(5))["stack_1"]["moe"]
    w = jax.tree.map(lambda a: a[0], w)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 24, cfg.d_model), jnp.float32)
    x = x.astype(jnp.bfloat16)
    shared = layers.mlp(w["shared"], x, "swiglu").astype(jnp.float32)
    total = shared
    pairs = 0
    for r in range(32):
        p = dict(w, wi=w["wi"][2 * r: 2 * r + 2], wo=w["wo"][2 * r: 2 * r + 2])
        out, counts = moe.moe_share(p, cfg.with_(expert_rank=r), x, impl="xla")
        total = total + (out.astype(jnp.float32) - shared)
        pairs += int(counts[0])
    assert pairs == 3 * 24 * 8  # every routed pair computed on exactly one rank
    whole = ref.moe(whole_conf, w, x.reshape(-1, cfg.d_model).astype(jnp.float32), False)
    whole = np.asarray(whole).reshape(total.shape)
    err = np.abs(np.asarray(total) - whole)
    # 32 bf16 partial outputs summed against one float32 layer
    assert err.max() < 0.05 * np.abs(whole).max()
    assert err.mean() < 0.01 * np.abs(whole).mean() + 1e-3


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The megablox grouped matmul (interpret mode) and ``lax.ragged_dot``
    give the same share, pairs and experts hit."""
    cfg, conf = _share_config(rank=3)
    w = jax.tree.map(lambda a: a[0],
                     ref.init_weights(conf, jax.random.PRNGKey(7))["stack_1"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 20, cfg.d_model)).astype(jnp.bfloat16)
    a, ca = moe.moe_share(w, cfg, x, impl="xla")
    b, cb = moe.moe_share(w, cfg, x, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_share_drops_no_token_where_capacity_would():
    """Every token sends its pairs to experts 0-7 (a large correction bias on
    them): the capacity rule of the training dispatch would keep 1.25 x
    T x k / E of them per expert; the share computes all and equals the
    per-expert reference."""
    cfg, conf = _share_config(rank=0, chips=1, held=64)
    w = jax.tree.map(lambda a: a[0],
                     ref.init_weights(conf, jax.random.PRNGKey(9))["stack_1"]["moe"])
    w["router_bias"] = w["router_bias"].at[:8].add(10.0)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 64, cfg.d_model)).astype(jnp.bfloat16)
    assert moe._capacity(cfg, 64) < 64  # the training dispatch would drop
    out, counts = moe.moe_share(w, cfg, x, impl="xla")
    assert int(counts[0]) == 64 * 8 and int(counts[1]) == 8
    want = ref.moe(conf, w, x[0].astype(jnp.float32), False)
    np.testing.assert_allclose(np.asarray(out[0], np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2 * float(np.abs(want).mean()))


# ------------------------------------------------------- transcriptions


def _noaux_tc_numpy(x, router, bias, n_group, topk_group, k, scaling):
    """DeepSeek-V3's ``MoEGate`` (noaux_tc), line by line in numpy."""
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router.astype(np.float64))))
    choice = scores + bias
    t, e = choice.shape
    group_scores = np.sort(choice.reshape(t, n_group, -1), axis=-1)[..., -2:].sum(-1)
    group_idx = np.argsort(-group_scores, axis=-1, kind="stable")[:, :topk_group]
    group_mask = np.zeros((t, n_group), bool)
    np.put_along_axis(group_mask, group_idx, True, axis=1)
    score_mask = np.repeat(group_mask, e // n_group, axis=1)
    tmp = np.where(score_mask, choice, -np.inf)
    idx = np.argsort(-tmp, axis=-1, kind="stable")[:, :k]
    weight = np.take_along_axis(scores, idx, 1)
    weight = weight / weight.sum(-1, keepdims=True)
    return weight * scaling, idx


def test_router_matches_a_numpy_transcription_of_noaux_tc():
    cfg = model.build_program(V3).cfg
    assert (cfg.n_experts, cfg.router_groups, cfg.router_topk_groups, cfg.top_k,
            cfg.routed_scaling) == (256, 8, 4, 8, 2.5)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(32, cfg.d_model)).astype(np.float32)
    router = (rng.normal(size=(cfg.d_model, 256)) / math.sqrt(cfg.d_model)).astype(np.float32)
    bias = (0.1 * rng.normal(size=256)).astype(np.float32)
    w, e, _ = moe.route({"router": jnp.asarray(router), "router_bias": jnp.asarray(bias)},
                        cfg, jnp.asarray(x))
    w_np, e_np = _noaux_tc_numpy(x, router, bias, 8, 4, 8, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(e), -1), np.sort(e_np, -1))
    order = np.argsort(np.asarray(e), -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1),
                               np.take_along_axis(w_np, np.argsort(e_np, -1), -1),
                               rtol=1e-5)
    # the bias moves the choice: without it another set of experts wins
    _, e_nobias = _noaux_tc_numpy(x, router, 0 * bias, 8, 4, 8, 2.5)
    assert (np.sort(e_nobias, -1) != np.sort(e_np, -1)).any()


def test_yarn_matches_a_numpy_transcription():
    """DeepSeek-V3's ``yarn_find_correction_range``, ``yarn_linear_ramp_mask``
    and ``yarn_get_mscale`` at the published values."""
    cfg = model.build_program(V3).cfg
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    got = layers.yarn_freqs(dim, base, factor, 32, 1, orig)
    np.testing.assert_allclose(np.asarray(got), inv, rtol=1e-6)
    mscale = 0.1 * math.log(factor) + 1.0
    assert layers.yarn_mscale(factor, 1.0) == pytest.approx(mscale)
    assert mla.sm_scale(cfg) == pytest.approx(mscale ** 2 / math.sqrt(192))
    assert mscale ** 2 == pytest.approx(1.8739, abs=1e-4)
    # the reference's own transcription agrees with both
    inv_ref, cos_sin, m2 = ref.yarn(V3)
    np.testing.assert_allclose(inv_ref, inv, rtol=1e-6)
    assert cos_sin == 1.0 and m2 == pytest.approx(mscale ** 2)
