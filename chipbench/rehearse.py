"""Rehearsal without the chip (run by hand, not a test):

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse [--layers 8 7 6] [--lanes 24 16]

Compiles the LM cells' programs at their real sizes for a DESCRIBED v5e
(`on-chip-measurement` section 2.3) and prints each program's
`memory_analysis`: it picks the LM training depth and checks that the
24-lane dense cache fits, at no chip time. A compile that passes is not a
chip run. The Gluon cell's programs are built inside `CachedOp` and cannot
be lowered from outside; PR 22's chip run holds its peak (9.56 GB at bs128).
"""

import argparse
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from jax.experimental import topologies      # noqa: E402
from jax.sharding import SingleDeviceSharding    # noqa: E402

from . import manifest                       # noqa: E402
from .reference import cerebras_gpt as ref   # noqa: E402
from .runners.lm_common import program_config    # noqa: E402

GB = 1e9


def _shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _report(what, compiled, resident=0.0):
    m = compiled.memory_analysis()
    args, out, temp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                       m.temp_size_in_bytes)
    alias = m.alias_size_in_bytes
    print("%-44s args %6.2f GB  out %6.2f  alias %6.2f  temp %6.2f  "
          "-> live %6.2f GB (+%.2f resident elsewhere)" % (
              what, args / GB, out / GB, alias / GB, temp / GB,
              (args + out - alias + temp) / GB, resident / GB))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, nargs="*", default=[8, 7, 6])
    ap.add_argument("--lanes", type=int, nargs="*", default=[24, 16])
    args = ap.parse_args()
    from mxnet_tpu.models import serving, transformer as tf
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    man = manifest.Manifest()

    train = man.config_of(man.cell("cerebras-gpt-1.3b-train-8k"))
    traffic = man.traffic_of(man.cell("cerebras-gpt-1.3b-train-8k"))
    for n in args.layers:
        config = dict(train, n_layer=n)
        cfg = program_config(config)
        params = jax.eval_shape(lambda: ref.as_tree(
            {k: jnp.zeros(s, jnp.bfloat16)
             for k, s, _ in ref.leaf_specs(config)}, config))
        mom = jax.eval_shape(tf.init_momentum, params)
        tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                      jnp.int32, sharding=one)
        step = tf.make_train_step(cfg)
        _report("train step, n_layer %d, %d x %d tokens" % (
            n, traffic["batch"], traffic["seq"]),
            step.lower(_shapes(params, one), _shapes(mom, one),
                       tokens).compile())

    serve = man.config_of(man.cell("cerebras-gpt-1.3b-serve-closed24"))
    cfg = program_config(serve)
    params = _shapes(jax.eval_shape(lambda: ref.as_tree(
        {k: jnp.zeros(s, jnp.bfloat16)
         for k, s, _ in ref.leaf_specs(serve)}, serve)), one)
    weights = sum(x.size * 2 for x in jax.tree.leaves(params))
    for lanes in args.lanes:
        cache = _shapes(jax.eval_shape(lambda: tf.init_cache(cfg, lanes)), one)
        vec = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one)
        keys = jax.ShapeDtypeStruct((lanes, 2), jnp.uint32, sharding=one)
        # the programs the batcher's defaults dispatch, built as it builds
        # them but with donation on (its _serving_donate asks the backend,
        # which is the CPU here)
        decode = jax.jit(
            lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg),
            donate_argnums=(1,))
        _report("decode step, %d lanes" % lanes,
                decode.lower(params, cache, vec, vec).compile())
        row = _shapes(jax.eval_shape(lambda: tf.init_cache(cfg, 1)), one)
        cache_bytes = sum(x.size * 2 for x in jax.tree.leaves(cache))
        for width in (2048, 512):
            toks = jax.ShapeDtypeStruct((1, width), jnp.int32, sharding=one)
            scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
            prefill = jax.jit(lambda p, c, t, s, r: tf.prefill_chunk(
                p, c, t, s, cfg, logits_row=r))
            _report("prefill row, width %d (beside %d lanes)" % (
                width, lanes),
                prefill.lower(params, row, toks, scalar, scalar).compile(),
                resident=cache_bytes)
        print("   weights %.2f GB + %d-lane cache %.2f GB = %.2f GB resident"
              % (weights / GB, lanes, cache_bytes / GB,
                 (weights + cache_bytes) / GB))
    del serving


if __name__ == "__main__":
    main()
