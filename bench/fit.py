"""Rehearsal without the chip, run by hand on the CPU: compile each cell's
programs for a described TPU v5e and print what its compiler says.

    JAX_PLATFORMS=cpu python3 bench/fit.py

For every cell of ``BENCHMARK.json``: the decode step at the cell's slots
and pool, and the prefill at its largest bucket, with their
``memory_analysis`` (bytes of arguments, outputs, temporaries and aliased
buffers on the chip).  Then the serving kernels alone at the shapes of
each cell's configuration (``paged_bitdecode``, the paged
``residual_flush``, ``kv_quant``, at the configuration's bits, block and
K granularity).  A compile that the chip's compiler refuses raises here.
Nothing runs, so nothing here is a time.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GB = 1e9


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args {m.argument_size_in_bytes / GB:.3f} GB, out {m.output_size_in_bytes / GB:.3f} GB, "
            f"temp {m.temp_size_in_bytes / GB:.3f} GB, alias {m.alias_size_in_bytes / GB:.3f} GB")


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model, traffic as tf
    from repro.serve.scheduler import bucket_for

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seen = {}
    for cell in bench["workloads"]:
        conf = model.load_config(cell["config"])
        mix = tf.load_mix(cell["traffic"])
        eng = conf["engine"]
        slots, max_seq, block = eng["slots"], eng["max_seq"], eng["kv_block"]
        prog = model.build_program(conf)
        params = on_chip(jax.eval_shape(prog.init, jax.random.PRNGKey(0)))
        nb_max = -(-max_seq // block)
        n_pages = slots * nb_max + slots
        state = on_chip(jax.eval_shape(
            lambda: prog.init_paged_decode_state(slots, n_pages=n_pages, nb_max=nb_max)))
        toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=chip)
        dec = jax.jit(lambda p, s, t: prog.decode_step(p, s, t, impl="pallas",
                                                       quant_impl="pallas")
                      ).lower(params, state, toks).compile()
        print(f"[fit] {cell['name']}: decode step, {slots} slots, {n_pages} pages: {_mem(dec)}",
              flush=True)
        bucket = bucket_for(int(mix["prompt_tokens"]["max"]), min_bucket=eng["min_bucket"])
        pt = jax.ShapeDtypeStruct((slots, bucket), jnp.int32, sharding=chip)
        ln = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        pre = jax.jit(lambda p, t, n: prog.prefill(p, {"tokens": t}, t.shape[1], lengths=n,
                                                   quant_impl="pallas")
                      ).lower(params, pt, ln).compile()
        print(f"[fit] {cell['name']}: prefill {slots} x {bucket}: {_mem(pre)}", flush=True)
        seen[conf["name"]] = conf

    from repro.kernels.kv_quant import ops as kvq
    from repro.kernels.paged_bitdecode import ops as pgd
    from repro.kernels.residual_flush import ops as rfl

    for conf in seen.values():
        h = conf["num_key_value_heads"]
        g = conf["num_attention_heads"] // h
        d, eng = conf["head_dim"], conf["engine"]
        bits, block, gran = eng["kv_bits"], eng["kv_block"], eng["kv_gran"]
        b, nb = eng["slots"], -(-eng["max_seq"] // block)
        n_pages = b + b * nb
        x = jax.ShapeDtypeStruct((1, h, n_pages * block, d), jnp.bfloat16, sharding=chip)
        kq = jax.eval_shape(lambda a: kvq.quantize_kv(a, bits, gran, block_n=block, impl="xla"), x)
        vq = jax.eval_shape(lambda a: kvq.quantize_kv(a, bits, "tensor", block_n=block, impl="xla"), x)
        pool = on_chip([jax.ShapeDtypeStruct((s.shape[2], s.shape[1], *s.shape[3:]), s.dtype)
                        for s in (*kq, *vq)])
        sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
        q = sd((b, h, g, d))
        res = (sd((b, h, block, d)), sd((b, h, block, d)))
        i32 = jnp.int32
        table, pack, rlen = sd((b, nb), i32), sd((b,), i32), sd((b,), i32)
        kw = dict(bits=bits, block_n=block, k_gran=gran, num_splits=1, impl="pallas")
        jax.jit(lambda *a: pgd.paged_bitdecode_attention(*a, **kw)).lower(
            q, *pool, *res, table, pack, rlen).compile()
        print(f"[fit] {conf['name']}: paged_bitdecode h={h} g={g} d={d} bits={bits}: compiles",
              flush=True)
        fkw = dict(bits=bits, block_n=block, k_gran=gran, impl="pallas")
        jax.jit(lambda *a: rfl.paged_residual_flush(*a, **fkw)).lower(
            *pool, *res, sd((b,), i32), sd((b,), i32)).compile()
        print(f"[fit] {conf['name']}: paged_residual_flush h={h} d={d}: compiles", flush=True)
        xk = sd((eng["slots"], h, 4096, d))
        jax.jit(lambda a: kvq.quantize_kv(a, bits, gran, block_n=block, impl="pallas")
                ).lower(xk).compile()
        print(f"[fit] {conf['name']}: kv_quant h={h} d={d} bits={bits}: compiles", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
