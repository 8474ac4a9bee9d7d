"""A configuration file (``bench/configs/<name>.json``) turned into the
program under test and its plain reference, both found by the file's
``family``: the model (``bench/builders/<family>.py``), the reference
(``bench/reference/<family>.py``), and the engine that serves the model
with weights the benchmark makes itself from the seed."""
from __future__ import annotations

import importlib
import json
import pathlib

import numpy as np

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def reference_module(conf: dict):
    """The plain reference of the configuration's family (``bench/reference``)."""
    return importlib.import_module(f"bench.reference.{conf['family']}")


def build_program(conf: dict):
    """The program's model object for ``conf``, from its family's builder
    (``bench/builders``)."""
    return importlib.import_module(f"bench.builders.{conf['family']}").build_program(conf)


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (run seeds may exceed 32 bits)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def check_tree(model, params) -> None:
    """The weights the benchmark made have the program's own layout."""
    import jax

    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's parameter tree")


def build_engine(conf: dict, model, params):
    """The serving engine of the timed path, as a deployment builds it."""
    from repro.serve.engine import ServeEngine

    eng = conf["engine"]
    return ServeEngine(model, params, slots=eng["slots"], max_seq=eng["max_seq"],
                       min_bucket=eng["min_bucket"])
