"""Long-context decoding: prefill a long prompt, then decode with the
quantized cache, comparing int4/int2 fidelity against an fp16-equivalent
(int8) baseline per decoded position — the paper's single-batch long-context
scenario (Fig. 11) at CPU-friendly scale.

Run:  PYTHONPATH=src python examples/longcontext_decode.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import smoke_config
from repro.core import qcache
from repro.models.zoo import build_model


def decode_n(model, params, state, tok, n):
    step = jax.jit(model.decode_step)
    ids, logps = [], []
    for _ in range(n):
        logits, state = step(params, state, tok)
        lp = jax.nn.log_softmax(logits[:, -1])
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        ids.append(int(tok[0, 0]))
        logps.append(np.asarray(lp)[0])
    return ids, np.stack(logps)


def main():
    base = smoke_config("llama3-8b")
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 384), 0, base.vocab)
    results = {}
    for bits in (8, 4, 2):
        cfg = base.with_(kv_bits=bits)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))  # same weights every run
        logits, state = jax.jit(lambda p, b: model.prefill(p, b, 640))(
            params, {"tokens": prompt})
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        cache = state["caches"][0]
        ids, logps = decode_n(model, params, qcache.to_pages(state), tok, 24)
        results[bits] = (ids, logps)
        kv_bytes = cache.kw.size * 4 * 2 + cache.k_res.size * 2 * 2
        print(f"int{bits}: cache≈{kv_bytes/1e6:.2f}MB  first tokens {ids[:8]}")

    ref_ids, ref_lp = results[8]
    # context: KL(ref || uniform) — how far the model is from noise; the
    # untrained smoke model has near-flat logits, so greedy-token agreement
    # is an unstable metric and KL is the meaningful one
    uni = -np.log(1.0 / ref_lp.shape[-1])
    kl_uniform = float(np.mean(np.sum(np.exp(ref_lp) * (ref_lp + uni), axis=-1)))
    print(f"reference sharpness: KL(int8||uniform) = {kl_uniform:.4f}")
    for bits in (4, 2):
        ids, lp = results[bits]
        agree = np.mean([a == b for a, b in zip(ids, ref_ids)])
        kl = float(np.mean(np.sum(np.exp(ref_lp) * (ref_lp - lp), axis=-1)))
        print(f"int{bits} vs int8 baseline: greedy-token agreement "
              f"{agree*100:.0f}% (untrained model — see above), "
              f"mean KL {kl:.4f} ({kl/max(kl_uniform,1e-9):.2f}x of uniform KL)")


if __name__ == "__main__":
    main()
