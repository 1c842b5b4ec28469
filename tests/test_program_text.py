"""The text of the programs the benchmark's older cells run, held to what
it was before latent attention learned to rotate: the shared path splits
by what a configuration states (a query rank, `rope`, a scaling record),
and a configuration that states none of them lowers to the program it
always had. Each case lowers one program of a real configuration from
shapes alone (nothing is allocated or compiled) and compares the hash of
its StableHLO text with the one taken at the commit before this file.

A change that means to alter one of these programs updates its hash:

    JAX_PLATFORMS=cpu python tests/test_program_text.py

prints them all, and says in PERF.md what the chip read afterwards.

PR 42 updated `kimi-linear.decode` on purpose: its two latent layers'
decode contraction became the kernel of kernels/latent_decode.py
(interpreted in the text a CPU lowers); the other nine are the parent's."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shapes(specs, float32=()):
    return {name: jax.ShapeDtypeStruct(
        shape, jnp.float32 if name.rsplit(".", 1)[-1] in float32
        else jnp.bfloat16) for name, shape, _ in specs}


def _sides(cell):
    """(program config, parameter shapes, lanes) of a serving cell."""
    from chipbench import manifest
    man = manifest.Manifest(ROOT)
    config = man.config_of(man.cell(cell))
    lanes = man.traffic_of(man.cell(cell)).get("clients")
    if config["runner"] == "serve_kimi_linear":
        from chipbench.reference import kimi_linear as ref
        from chipbench.runners.serve_kimi_linear import program_config
        shapes = _shapes(ref.leaf_specs(config), ref.FLOAT32_LEAVES)
    elif config["runner"] == "serve_jamba":
        from chipbench.reference import jamba as ref
        from chipbench.runners.serve_jamba import program_config
        shapes = _shapes(ref.leaf_specs(config))
    else:
        from chipbench.reference import cerebras_gpt as ref
        from chipbench.runners.lm_common import program_config
        shapes = _shapes(ref.leaf_specs(config))
    return program_config(config), ref.as_tree(shapes, config), lanes


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _decode(cell):
    from mxnet_tpu.models import serving, transformer as tf
    cfg, params, lanes = _sides(cell)
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, lanes))
    fn = serving._jitted_pipeline_chunk(cfg, True, 1.0, 0, 1.0, 1, False)
    return fn.lower(params, cache, None, _i32(lanes), _i32(lanes),
                    jax.ShapeDtypeStruct((lanes, 2), jnp.uint32))


def _admission(cell, width):
    from mxnet_tpu.models import transformer as tf
    cfg, params, _ = _sides(cell)
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    return tf._jitted_prefill_chunk_row(cfg).lower(
        params, row, _i32(1, width), _i32(), _i32())


def _prefill(cell, width):
    from mxnet_tpu.models import transformer as tf
    cfg, params, _ = _sides(cell)
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    return tf._jitted_prefill(cfg).lower(params, row, _i32(1, width))


def _forward(cell, width):
    from mxnet_tpu.models import transformer as tf
    cfg, params, _ = _sides(cell)
    return jax.jit(lambda p, t: tf.forward(p, t, cfg)).lower(
        params, _i32(1, width))


def _train_step(cell):
    from mxnet_tpu.models import transformer as tf
    cfg, params, _ = _sides(cell)
    mom = jax.eval_shape(tf.init_momentum, params)
    return tf.make_train_step(cfg).lower(params, mom, _i32(4, 2048))


KL, JA, CE = ("kimi-linear-48b-serve-reason32", "jamba2-3b-serve-chat64",
              "cerebras-gpt-1.3b-serve-closed24")
PROGRAMS = {
    "kimi-linear.decode": (_decode, KL),
    "kimi-linear.admission-1024": (_admission, KL, 1024),
    "kimi-linear.admission-8192": (_admission, KL, 8192),
    "kimi-linear.prefill-64": (_prefill, KL, 64),
    "kimi-linear.forward-256": (_forward, KL, 256),
    "jamba.decode": (_decode, JA),
    "jamba.admission-256": (_admission, JA, 256),
    "cerebras.decode": (_decode, CE),
    "cerebras.admission-512": (_admission, CE, 512),
    "cerebras.train-step": (_train_step, "cerebras-gpt-1.3b-train-8k"),
}
# sha256 of the StableHLO text, first 16 hex digits, at the parent commit
# (of PR 41; one line says where a later PR moved it)
AT_THE_PARENT = {
    "cerebras.admission-512": "aba915c111ccf1db",
    "cerebras.decode": "59e8d1ef85648873",
    "cerebras.train-step": "42a91385a6f26e39",
    "jamba.admission-256": "1deb540c67da2cc8",
    "jamba.decode": "169f587ab80ff84e",
    "kimi-linear.admission-1024": "266ea9a975fca4c1",
    "kimi-linear.admission-8192": "02be86bc0f1a7253",
    "kimi-linear.decode": "7441c29a6496fec8",   # PR 42: mla_decode
    "kimi-linear.forward-256": "e346f4bf91718edf",
    "kimi-linear.prefill-64": "3169c5673d754d47",
}


def text_hash(name):
    build, *args = PROGRAMS[name]
    return hashlib.sha256(build(*args).as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_a_configuration_that_states_nothing_new_keeps_its_program(name):
    assert text_hash(name) == AT_THE_PARENT[name]


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    ROOT = os.getcwd()
    for name in sorted(PROGRAMS):
        print('    "%s": "%s",' % (name, text_hash(name)), flush=True)
