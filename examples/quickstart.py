"""Quickstart: build a small llama-family model, prefill a prompt into the
quantized KV cache, hand it to the paged decode layout, and greedily decode
a few tokens — the minimal BitDecoding pipeline (query transform -> residual
append -> fused low-bit attention).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import smoke_config
from repro.core import qcache
from repro.models.zoo import build_model


def main():
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_gran="channel")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"model: {cfg.name}-smoke  kv_bits={cfg.kv_bits} "
          f"({cfg.kv_gran}-wise K scaling, residual N_r={cfg.kv_block})")

    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, cfg.vocab)
    logits, state = jax.jit(lambda p, b: model.prefill(p, b, 256))(
        params, {"tokens": prompt})
    cache0 = state["caches"][0]  # stacked over layers: leaves are [L, B, ...]
    print(f"prefilled {prompt.shape[1]} tokens; cache length = "
          f"{int(cache0.length[0, 0])} "
          f"(packed blocks={int(cache0.pack_blocks[0, 0])}, "
          f"residual={int(cache0.res_len[0, 0])})")

    state = qcache.to_pages(state)  # decode reads pages (identity page table)
    step = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    for _ in range(16):
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    print("greedy continuation token ids:", out)
    print("final cache length:", int(jnp.max(state["caches"][0].length[0])))


if __name__ == "__main__":
    main()
