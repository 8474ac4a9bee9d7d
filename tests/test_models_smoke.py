"""Per-architecture smoke tests: reduced same-family configs, one train step
+ prefill + decode on CPU; asserts output shapes and finiteness, plus
prefill↔decode logit consistency for cache-bearing archs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import _REGISTRY, smoke_config
from repro.core import qcache
from repro.models.zoo import build_model

ARCHS = [n for n in _REGISTRY]


def make_batch(cfg, B=2, S=40, key=1):
    t = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab)
    b = {"tokens": t, "labels": t, "loss_mask": jnp.ones((B, S), jnp.float32)}
    if cfg.encdec:
        b["frames"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, 24, cfg.d_model), jnp.bfloat16
        )
    if cfg.vision_stub:
        b["patches"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, cfg.n_patches, cfg.d_model), jnp.bfloat16
        )
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_train_step(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)

    loss, grads = jax.jit(jax.value_and_grad(m.loss))(params, batch)
    assert np.isfinite(float(loss)), f"{arch} loss not finite"
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, f"{arch} bad grads"


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_prefill_decode(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 40
    batch = make_batch(cfg, B, S)

    logits, state = jax.jit(lambda p, b: m.prefill(p, b, 192))(params, batch)
    assert logits.shape == (B, 1, cfg.vocab)
    state = qcache.to_pages(state)
    step = jax.jit(m.decode_step)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for _ in range(3):
        logits, state = step(params, state, tok)
        assert logits.shape == (B, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits)).all(), f"{arch} decode logits not finite"
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


@pytest.mark.parametrize("arch", ["llama3_8b", "deepseek_v3_671b", "qwen2_vl_7b"])
def test_prefill_decode_consistency(arch):
    """Last-token logits via prefill(S) == prefill(S-1) + decode_step(token).

    With S < kv_block the history sits in the bf16 residual, so the decode
    path must agree with the fp16 prefill attention almost exactly."""
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 40
    batch = make_batch(cfg, B, S)
    lf, _ = jax.jit(lambda p, b: m.prefill(p, b, 128))(params, batch)

    bm1 = dict(batch)
    bm1["tokens"] = batch["tokens"][:, :-1]
    bm1["labels"] = batch["labels"][:, :-1]
    bm1["loss_mask"] = batch["loss_mask"][:, :-1]
    lp, state = jax.jit(lambda p, b: m.prefill(p, b, 128))(params, bm1)
    ld, _ = jax.jit(m.decode_step)(params, qcache.to_pages(state),
                                   batch["tokens"][:, -1:])
    np.testing.assert_allclose(
        np.asarray(lf[:, 0]), np.asarray(ld[:, 0]), rtol=2e-2, atol=3e-1
    )
