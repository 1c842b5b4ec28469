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
(interpreted in the text a CPU lowers).

PR 44 re-pinned all five Kimi-Linear programs on purpose, and pinned two
programs each of the other two configurations with a router (Kimi-K2.6,
Xing4.0) at its own text: every expert layer's three grouped matmuls
became the kernel of kernels/grouped_matmul.py behind _expert_ffn where
they were jax.lax.ragged_dot. The five programs of the configurations
WITHOUT a router (Cerebras-GPT, Jamba2) never reach that call and keep
the hashes they had at PR 41's parent.

PR 45 updated the three decode programs with a latent layer on purpose
(`kimi-k2.decode`, `kimi-linear.decode`, `xing4.decode`): a step's store
of a latent layer's fresh `kr` rows became the writer of
kernels/latent_decode.py (latent_row_store, in place) where it was a
scatter. Their admissions, prefills and forward store a chunk at a
scalar start and keep their text, as does every program without a
latent layer.

PR 47 pinned three more programs of the accepted configurations at the
text they had at its parent (a prefill of each K/V configuration and the
hybrid forward: `prefill`, `_attention` and `_cache_attend` gained a
window and a rotation flag that neither states), and three of the new
configuration (SmallThinker: window layers, a ring, the blocked chunk
contraction) at its own text. All fourteen older hashes stand.

PR 48 updated `cerebras.decode` and `smallthinker.decode` on purpose:
decode's contraction over dense K/V rows became the kernel of
kernels/kv_decode.py (one pass over each lane's rows up to its position)
in Cerebras-GPT's 24 layers and SmallThinker's two full layers, where
the rows' shape gives that kernel a block (kernels.kv_decode.kv_block).
`jamba.decode` stands: its one K/V head's rows are so narrow that a
block would be the whole cache, and the XLA text stays, letter for
letter (kv_decode_reference), as it does for SmallThinker's six rings.
The other seventeen hashes stand too: no admission, prefill, forward or
train step reaches that call, and latent and KDA mixers bypass it.

PR 50 pinned three programs of the new configuration (Nemotron-3-Nano:
blocks of one sub-layer, the Mamba-2 mixer of models/ssd.py, relu2
experts at the width `pad_expert_width` serves them, 1,920, where the
grouped matmuls are the kernel) at its own text. All twenty older hashes stand: `_layer` runs
the mixer where the block has one and the feed-forward where its
parameters hold "ln2", `_mlp` / `_expert_ffn` take the ungated form from
a table, and a configuration that states neither `mixer_ffn=False`, an
"ffn" or "mamba2" block nor `ffn="relu2"` lowers to the text it had.

PR 51 re-pinned `cerebras.train-step` on purpose: the causal attention
of its six layers, forward and backward, became the two kernels of
kernels/flash_attention.py (flash_fwd, flash_bwd; interpreted in the
text a CPU lowers) where it was XLA's float32 [4, 16, 2048, 2048] score
plane, by the rule of shapes behind _causal_attention
(transformer.causal_attention_blocks: heads a multiple of 128 wide, T a
multiple of 128 from kernels.flash_attention.MIN_SEQ on, one shape and
dtype, no window, no mesh). The other twenty-two hashes stand, the three
`*.forward-256` and the three `*.prefill-*` among them: their T lies
under the crossover (and Kimi-Linear's latent layers hold two head
widths, Jamba2's and Nemotron's forwards 256 positions), so they lower
to the XLA text, letter for letter; no decode or admission program
reaches _causal_attention at all.

PR 53 re-pinned on purpose the two admission programs of the K/V
configurations whose width reaches kernels.chunk_attention.MIN_QUERIES
(1,024): `smallthinker.admission-8192` and `nemotron.admission-2048`.
The chunk contraction of their "attention" layers against the cached
rows became the kernel of kernels/chunk_attention.py (chunk_attn;
interpreted in the text a CPU lowers) where it was XLA's float32 score
plane [1, C, KVH, G, T], or for SmallThinker's chunk of 8,192 the XLA
blocks, by the rule of shapes behind _cached_attention
(transformer.chunk_attention_blocks: a query of the rows' dtype, heads a
multiple of 128 wide, 128 dividing chunk and rows, a chunk of 1,024
queries or more, [C] positions, no window, no int8). It pinned four
more: `cerebras.admission-1024` (the admission the cell's p95 gap waits
behind) and `jamba.admission-1024` at their own text, with the kernel,
and `cerebras.admission-128` and `jamba.admission-128` at the text of
its parent (c1914b4). The other twenty-one hashes stand:
`cerebras.admission-512`, `jamba.admission-256` and
`smallthinker.admission-256` lie under the floor and keep the plane,
letter for letter (the floor was 256 while this PR was measured and
went to 1,024 by what the chip read: PERF.md section 6); every decode,
prefill, forward, `*.forward-256` and train step; and the five
admissions of the latent configurations, whose chunks contract through
_latent_chunk_attention. SmallThinker's six window layers keep
_blocked_attention inside its re-pinned program."""

import hashlib
import importlib
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


# a configuration's runner -> its reference (others: Cerebras-GPT's pair)
REFERENCES = {"serve_kimi_linear": "kimi_linear", "serve_kimi_k2": "kimi_k2",
              "serve_xing4": "xing4", "serve_jamba": "jamba",
              "serve_smallthinker": "smallthinker",
              "serve_nemotron_h": "nemotron_h"}


def _sides(cell):
    """(program config, parameter shapes, lanes) of a serving cell."""
    from chipbench import manifest
    man = manifest.Manifest(ROOT)
    config = man.config_of(man.cell(cell))
    lanes = man.traffic_of(man.cell(cell)).get("clients")
    ref = importlib.import_module(
        "chipbench.reference."
        + REFERENCES.get(config["runner"], "cerebras_gpt"))
    runner = importlib.import_module(
        "chipbench.runners." + (config["runner"] if config["runner"]
                                in REFERENCES else "lm_common"))
    shapes = _shapes(ref.leaf_specs(config),
                     getattr(ref, "FLOAT32_LEAVES", ()))
    if config["runner"] == "serve_xing4":    # a frame's leaves in three
        params = jax.eval_shape(
            lambda w: runner.program_params(w, config), shapes)
    elif config["runner"] == "serve_nemotron_h":    # the experts padded
        from mxnet_tpu.models import transformer as tf
        params = jax.eval_shape(
            lambda w: runner.program_sides(config, 0, w)[0], shapes)
        return tf.pad_expert_width({"layers": []}, runner.program_config(
            config))[1], params, lanes
    else:
        params = ref.as_tree(shapes, config)
    return runner.program_config(config), params, lanes


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
K2, XI = "kimi-k2.6-serve-agent32", "xing4.0-29b-a4b-serve-rag32"
SM = "smallthinker-21b-serve-docchat32"
NE = "nemotron3-nano-serve-subagent64"
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
    "kimi-k2.decode": (_decode, K2),
    "kimi-k2.admission-4096": (_admission, K2, 4096),
    "xing4.decode": (_decode, XI),
    "xing4.admission-2048": (_admission, XI, 2048),
    "jamba.prefill-256": (_prefill, JA, 256),
    "jamba.forward-256": (_forward, JA, 256),
    "cerebras.prefill-512": (_prefill, CE, 512),
    "smallthinker.decode": (_decode, SM),
    "smallthinker.admission-8192": (_admission, SM, 8192),
    "smallthinker.admission-256": (_admission, SM, 256),
    "nemotron.decode": (_decode, NE),
    "nemotron.admission-2048": (_admission, NE, 2048),
    "nemotron.forward-256": (_forward, NE, 256),
    "cerebras.admission-128": (_admission, CE, 128),
    "cerebras.admission-1024": (_admission, CE, 1024),
    "jamba.admission-128": (_admission, JA, 128),
    "jamba.admission-1024": (_admission, JA, 1024),
}
# sha256 of the StableHLO text, first 16 hex digits, at the parent commit
# (of PR 41; a line says where a later PR moved or first pinned it)
AT_THE_PARENT = {
    "cerebras.admission-512": "aba915c111ccf1db",
    "cerebras.decode": "f8fe6738e9d6206d",        # PR 48: kv_decode
    "cerebras.train-step": "63f4be28e7c62c2c",    # PR 51: flash_fwd/bwd
    "jamba.admission-256": "1deb540c67da2cc8",
    "jamba.decode": "169f587ab80ff84e",
    # PR 44: the expert layers' grouped matmuls are the kernel moe_gmm
    "kimi-linear.admission-1024": "6e1223dfd86d2b04",
    "kimi-linear.admission-8192": "faf23b2bd0db3785",
    # and PR 42: mla_decode; PR 45: mla_row_store
    "kimi-linear.decode": "d232a191db71c85b",
    "kimi-linear.forward-256": "f8167ddf6b0b1846",
    "kimi-linear.prefill-64": "936d76aa83f8a957",
    # first pinned at PR 44, with the kernel
    "kimi-k2.admission-4096": "06e7c3dc780429ff",
    "kimi-k2.decode": "2c34bb63450d0158",       # PR 45: mla_row_store
    "xing4.admission-2048": "545b1a30387f51d9",
    "xing4.decode": "5b9cc8432b52af5d",         # PR 45: mla_row_store
    # first pinned at PR 47, at the text of its parent (c1930bc)
    "cerebras.prefill-512": "29a56c94f0c64e13",
    "jamba.forward-256": "7859eda083ed7efe",
    "jamba.prefill-256": "eb93d3a29c28a6da",
    # first pinned at PR 47, at its own text
    "smallthinker.admission-256": "5c3d4288cbea8573",
    "smallthinker.admission-8192": "537b9be32ba7e7a7",    # PR 53: chunk_attn
    "smallthinker.decode": "af1da804614b0d21",    # PR 48: kv_decode
    # first pinned at PR 50, at its own text
    "nemotron.admission-2048": "72e4d63e20d07ed4",    # PR 53: chunk_attn
    "nemotron.decode": "40d112c918c1fe5f",
    "nemotron.forward-256": "c3dda5037fbe78a0",
    # first pinned at PR 53: with the kernel, and under its floor at the
    # text of its parent (c1914b4)
    "jamba.admission-1024": "ba8e9c8b1c5f6eb0",
    "cerebras.admission-1024": "5af26d04bc3a4509",
    "cerebras.admission-128": "a958bd68df7474f9",
    "jamba.admission-128": "2ed0d58d9aa7940b",
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
