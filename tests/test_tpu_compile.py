"""Ask the TPU's compiler, without a chip, for every Pallas kernel at
the shapes chip_smoke.py runs (on-chip-measurement guide §2, rehearsal
3). Interpret mode proves a kernel's arithmetic; only Mosaic says
whether it lowers (tiling, VMEM, partitioning) — paged_attention passed
22 interpret tests and had never lowered.

The topology is described inside a module-scoped fixture (never at
import: only one process may load libtpu, and every xdist worker
imports every test file), the compiles run in the test's own process,
and the persistent compilation cache is off around them (a compile for
a described device is written to the cache but cannot be read back)."""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from mxnet_tpu.kernels.paged_decode import paged_attention
from mxnet_tpu.parallel import ring

# the package re-exports the function under the module's own name
fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")

B, T, H, D = 4, 2048, 16, 128          # flash: the LM training cell's q, k, v
DEC_B, DEC_T = 8, 4096                 # decode: bs 8 against a 4k cache
NB, BS, KVH = 2048, 16, 4              # paged pool [NB, BS, KVH, D]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Lower + compile for the described chip; the kernel must be in
    the program as a Mosaic custom call, not interpreted jnp."""
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), \
        "no Mosaic kernel in the lowered program"
    return lowered.compile()


def _sds(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _moved(text, shapes):
    """The lines of a compiled program's text that copy or transpose an
    array of one of `shapes` (as the text spells them)."""
    return [line for line in text.splitlines()
            if any(op in line for op in (" copy(", " transpose(",
                                         " copy-start("))
            and any(shape in line for shape in shapes)]


def _prefetch(line):
    """Whether a `copy-start` line only moves an array to another memory
    space (XLA's own prefetch into the alternate memory, `S(n)` in the
    layout) and keeps its order: no relayout, and off the critical path."""
    import re
    shapes = re.findall(r"[a-z0-9]+\[[0-9,]*\]\{[^}]*\}",
                        line.split(" copy-start(")[0])
    return " copy-start(" in line and len(shapes) >= 2 \
        and re.sub(r"S\(\d\)", "", shapes[0]) \
        == re.sub(r"S\(\d\)", "", shapes[1])


def test_flash_attention_forward(one_chip):
    q = _sds(one_chip, (B, T, H, D))
    _compile(functools.partial(fa.flash_attention, causal=True,
                               interpret=False), q, q, q)


@pytest.mark.parametrize("batch,rows", [(B, T), (1, 16384), (8, None)],
                         ids=["cell", "longest", "shortest"])
def test_flash_attention_forward_backward(one_chip, batch, rows):
    """At the training cell's shape and at the ends of the rule: the
    longest sequence flash_blocks hands the kernels (a head's dQ
    accumulator is 8 MB of the backward's VMEM) and the shortest."""
    rows = rows or fa.MIN_SEQ
    assert fa.flash_blocks(rows, D, 2) is not None
    assert fa.flash_blocks(2 * 16384, D, 2) is None
    q = _sds(one_chip, (batch, rows, H, D))

    def loss(q_, k_, v_):
        o = fa.flash_attention(q_, k_, v_, causal=True, interpret=False)
        return o.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_cerebras_train_step_holds_no_score_plane(one_chip, monkeypatch):
    """The WHOLE training step of cerebras-gpt-1.3b-train-8k at its
    [4, 2048] tokens: every layer's causal attention is the two flash
    kernels (6 forward + 6 fused backward calls), no [B, H, T, T] array
    exists anywhere in the compiled program, q, k, v, o and their
    gradients pass between the projections' matmuls and the kernels in
    the order the matmuls leave them (no copy or transpose of an array
    of their size), and the temporaries are 5.41 GB where the XLA text's
    six saved planes made them 11.87."""
    import json
    import os
    from chipbench.reference import cerebras_gpt as ref
    from chipbench.runners import lm_common
    from mxnet_tpu.models import transformer as tf
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "cerebras-gpt-1.3b-train.json")) as f:
        config = json.load(f)
    params = ref.as_tree({leaf: _sds(one_chip, shape) for leaf, shape, _
                          in ref.leaf_specs(config)}, config)
    mom = jax.tree.map(lambda x: _sds(one_chip, x.shape, jnp.float32),
                       params)
    step = tf.make_train_step(lm_common.program_config(config), lr=0.01)
    compiled = step.lower(params, mom,
                          _sds(one_chip, (B, T), jnp.int32)).compile()
    text = compiled.as_text()
    layers = config["n_layer"]
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * layers
    assert text.count("flash_fwd/pallas_call") >= layers \
        and text.count("flash_bwd/pallas_call") >= layers
    assert "[%d,%d,%d,%d]" % (B, H, T, T) not in text
    moved = [line for line in _moved(
        text, ("[%d,%d,%d,%d]" % (B, T, H, D), "[%d,%d,%d,%d]" % (B, H, T, D)))
        if not _prefetch(line)]
    assert not moved, moved
    mem = compiled.memory_analysis()
    print("cerebras train step: temporaries %d bytes, arguments %d"
          % (mem.temp_size_in_bytes, mem.argument_size_in_bytes))
    assert mem.temp_size_in_bytes < 6e9


@pytest.mark.parametrize("kv_heads", [H, 2], ids=["mha", "gqa"])
def test_flash_decode(one_chip, kv_heads):
    q = _sds(one_chip, (DEC_B, H, D))
    cache = _sds(one_chip, (DEC_B, DEC_T, kv_heads, D))
    lengths = _sds(one_chip, (DEC_B,), jnp.int32)
    _compile(functools.partial(fa.flash_decode, interpret=False),
             q, cache, cache, lengths)


def test_flash_carry_block(one_chip):
    """The ring's per-round update at an sp=4 shard of T 2048."""
    bh, t_shard = B * H, T // 4
    q = _sds(one_chip, (bh, t_shard, D))
    o = _sds(one_chip, (bh, t_shard, D), jnp.float32)
    ml = _sds(one_chip, (bh, t_shard), jnp.float32)
    off = _sds(one_chip, (), jnp.int32)
    _compile(functools.partial(fa.flash_carry_block, causal=True,
                               interpret=False),
             q, q, q, o, ml, ml, off, off)


def test_ring_attention_flash_under_shard_map(topo, monkeypatch):
    """flash_carry_block as ring attention really calls it: inside a
    vma-checked shard_map over sp=4, ppermutes between rounds. The
    ring picks compiled-vs-interpret from jax.default_backend(), which
    here still says cpu, so the test steers that one question."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("sp",))
    spec = P(None, "sp", None, None)
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    fn = jax.shard_map(
        functools.partial(ring.ring_attention, axis_name="sp",
                          causal=True, use_flash_kernel=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={"sp"})
    compiled = _compile(fn, q, q, q)
    assert "collective-permute" in compiled.as_text()


@pytest.mark.parametrize("span", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_attention(one_chip, int8, span):
    kv = _sds(one_chip, (NB, BS, KVH, D),
              jnp.int8 if int8 else jnp.bfloat16)
    pool = {"k": kv, "v": kv}
    if int8:
        sc = _sds(one_chip, (NB, BS, KVH), jnp.float32)
        pool.update(ks=sc, vs=sc)
    q = _sds(one_chip, (DEC_B, span, H, D))
    tables = _sds(one_chip, (DEC_B, DEC_T // BS), jnp.int32)
    pos = _sds(one_chip, (DEC_B,), jnp.int32)
    _compile(functools.partial(paged_attention, interpret=False),
             q, pool, tables, pos)


@pytest.mark.parametrize("heads,rows", [(64, 19456), (32, 11264)],
                         ids=["kimi-k2.6", "kimi-linear"])
def test_latent_decode(one_chip, heads, rows):
    """The decode contraction on every "mla" layer's path, at the two
    Kimi cells' real shapes: 32 lanes, rank-512 latents and a 64-wide
    shared key part. The
    chip keeps `kr` with T minor, the order the kernel's block reads it
    in: no whole-array copy may stand in front of the call."""
    from mxnet_tpu.kernels.latent_decode import latent_decode
    lanes = 32
    compiled = _compile(
        functools.partial(latent_decode, norm=float(np.sqrt(192.0)),
                          interpret=False),
        _sds(one_chip, (lanes, heads, 512)), _sds(one_chip, (lanes, heads, 64)),
        _sds(one_chip, (lanes, rows, 512)), _sds(one_chip, (lanes, rows, 64)),
        _sds(one_chip, (lanes,), jnp.int32))
    assert "mla_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("rows", [96, 192, 384, 20096],
                         ids=["one-block-96", "one-block-192",
                              "three-of-128", "157-of-128"])
def test_latent_decode_at_cache_lengths_no_cell_has(one_chip, rows):
    """At Kimi-K2.6's widths: one block that is no multiple of 128 (the
    whole array, so Mosaic takes `kr`'s block with its rows on the
    lanes) and blocks of 128, the smallest there is."""
    from mxnet_tpu.kernels.latent_decode import latent_decode
    lanes = 4
    _compile(
        functools.partial(latent_decode, norm=float(np.sqrt(192.0)),
                          interpret=False),
        _sds(one_chip, (lanes, 64, 512)), _sds(one_chip, (lanes, 64, 64)),
        _sds(one_chip, (lanes, rows, 512)), _sds(one_chip, (lanes, rows, 64)),
        _sds(one_chip, (lanes,), jnp.int32))


@pytest.mark.parametrize("heads,rows", [(64, 19456), (32, 11264),
                                        (32, 9216)],
                         ids=["kimi-k2.6", "kimi-linear", "xing4.0"])
def test_latent_row_store_feeds_latent_decode_in_place(one_chip, heads, rows):
    """A decode round's store of the fresh `kr` rows and the contraction
    behind it, at the three latent cells' shapes with the leaf donated
    as the decode programs donate the cache: the writer takes and leaves
    the leaf in the order the chip keeps it and `mla_decode` reads it,
    so no copy or transpose of an array of the leaf's size stands before,
    between or after them (the scatter it replaces had two, 152 MB of
    temporaries at Kimi-K2.6's shape) and nothing of that size is a
    temporary."""
    from mxnet_tpu.kernels.latent_decode import (latent_decode,
                                                 latent_row_store)
    lanes = 32

    def store_and_attend(kr, fresh, pos, q_lat, q_r, c):
        kr = latent_row_store(kr, fresh, pos, interpret=False)
        return kr, latent_decode(q_lat, q_r, c, kr, pos + 1,
                                 float(np.sqrt(192.0)), interpret=False)

    lowered = jax.jit(store_and_attend, donate_argnums=(0,)).lower(
        _sds(one_chip, (lanes, rows, 64)), _sds(one_chip, (lanes, 64)),
        _sds(one_chip, (lanes,), jnp.int32),
        _sds(one_chip, (lanes, heads, 512)), _sds(one_chip, (lanes, heads, 64)),
        _sds(one_chip, (lanes, rows, 512)))
    assert lowered.as_text().count("tpu_custom_call") >= 2
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "mla_row_store" in text and "mla_decode" in text
    assert "scatter" not in text
    moved = _moved(text, ("[%d,%d,64]" % (lanes, rows),
                          "[%d,64,%d]" % (lanes, rows)))
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("rows", [96, 192, 384],
                         ids=["one-block-96", "one-block-192",
                              "three-of-128"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_latent_row_store_at_cache_lengths_no_cell_has(one_chip, dtype, rows):
    """One tile that is no multiple of 128 (the whole cache, so Mosaic
    takes it with its rows on the lanes) and tiles of 128."""
    from mxnet_tpu.kernels.latent_decode import latent_row_store
    lanes = 4
    compiled = _compile(
        functools.partial(latent_row_store, interpret=False),
        _sds(one_chip, (lanes, rows, 64), dtype),
        _sds(one_chip, (lanes, 64), dtype),
        _sds(one_chip, (lanes,), jnp.int32))
    assert "mla_row_store" in compiled.as_text()


def test_an_untileable_latent_cache_compiles_as_xla(one_chip):
    """A long cache that 128 does not divide (20,000 rows) has no
    kernel: transformer._latent_decode_attention keeps the XLA text for
    it, which compiles for the chip, so a decode the parent ran at any
    max_len still runs."""
    from mxnet_tpu.models import transformer as tf
    rows, lanes = 20000, 4
    cfg = tf.TransformerConfig(
        n_heads=64, max_len=rows, rope=False, positions="none",
        layer_kinds=("mla",) * 2, mla_rank=512, mla_nope_dim=128,
        mla_rope_dim=64, mla_v_dim=128)
    lowered = jax.jit(
        lambda q, c, kr, pos, w: tf._latent_decode_attention(
            q, {"c": c, "kr": kr}, pos, {"wkvb": w}, cfg)).lower(
        _sds(one_chip, (lanes, 64, 192)), _sds(one_chip, (lanes, rows, 512)),
        _sds(one_chip, (lanes, rows, 64)),
        _sds(one_chip, (lanes,), jnp.int32),
        _sds(one_chip, (512, 64, 256)))
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text
    lowered.compile()


KV_SHAPES = {
    # lanes, rows, K/V heads, query heads a K/V head
    "cerebras-gpt": (24, 2048, 16, 1),
    "smallthinker-full-layer": (32, 16384, 4, 7),
}


@pytest.mark.parametrize("lanes,rows,kvh,group", KV_SHAPES.values(),
                         ids=KV_SHAPES.keys())
def test_kv_decode(one_chip, lanes, rows, kvh, group):
    """The decode contraction over dense K/V rows at the two cells' real
    shapes, blocks of 256 and 1,024 positions of all K/V heads read from
    the 4-D leaf where it lies (the chip keeps `[.., 16, 128]` bf16 in
    its own (16, 128) tiles and `[.., 4, 128]` in tiles of one
    position's 4 heads; Mosaic takes either as the block's last two
    dimensions): no copy or transpose of an array of a leaf's size
    stands before the call and nothing of that size is a temporary."""
    from mxnet_tpu.kernels.kv_decode import kv_decode
    leaf = _sds(one_chip, (lanes, rows, kvh, 128))
    compiled = _compile(
        functools.partial(kv_decode, interpret=False),
        _sds(one_chip, (lanes, kvh * group, 128)), leaf, leaf,
        _sds(one_chip, (lanes,), jnp.int32))
    text = compiled.as_text()
    assert "kv_decode" in text
    moved = _moved(text, ("[%d,%d,%d,128]" % (lanes, rows, kvh),))
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


CHUNK_SHAPES = {
    # queries, rows, query heads, K/V heads
    "cerebras-256": (256, 2048, 16, 16),
    "cerebras-512": (512, 2048, 16, 16),
    "cerebras-1024": (1024, 2048, 16, 16),
    "cerebras-2048": (2048, 2048, 16, 16),
    "smallthinker-full-layer": (8192, 16384, 28, 4),
    "nemotron": (8192, 8192, 32, 2),
    "jamba2": (2048, 4096, 20, 1),
}


def _relaid(text, lines):
    """Of `lines` (_moved), those that change an array's order: not
    XLA's prefetch into the alternate memory (_prefetch), and not a
    `copy` whose result has its operand's layout but for the memory
    space (`S(n)`)."""
    import re

    def layout(spelt):
        return re.sub(r"S\(\d\)", "", spelt)

    out = []
    for line in lines:
        if _prefetch(line):
            continue
        made = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) copy\(%([^),]+)\)", line)
        source = made and re.search(
            r"%%%s = (\S+) " % re.escape(made.group(2)), text)
        if source and layout(made.group(1)) == layout(source.group(1)):
            continue
        out.append(line)
    return out


@pytest.mark.parametrize("queries,rows,heads,kvh", CHUNK_SHAPES.values(),
                         ids=CHUNK_SHAPES.keys())
def test_chunk_attention(one_chip, queries, rows, heads, kvh):
    """A chunk's contraction against cached K/V rows at the Cerebras
    cell's four bucket widths (the route hands the kernel the two wide
    ones: kernels.chunk_attention.MIN_QUERIES; its own door takes all
    four) and at the widest chunk of the three other K/V configurations: the rows are read from the 4-D leaf where it lies
    (a block of up to 1,024 positions of all K/V heads; one head's rows are a
    strided load inside the kernel; Jamba2's one head as the [T, D] the
    chip keeps it as), so no copy or transpose of an array of a leaf's
    size stands before the call, and nothing is a temporary: no score
    plane, at any width. The queries go in and come out heads-first, the
    order the kernel works in and XLA gives a projection's result
    (test_cerebras_admission_holds_no_score_plane)."""
    from mxnet_tpu.kernels.chunk_attention import chunk_attention
    leaf = _sds(one_chip, (1, rows, kvh, 128))

    def heads_first(q, k, v, start):
        return chunk_attention(q.transpose(0, 2, 1, 3), k, v, start,
                               interpret=False).transpose(0, 2, 1, 3)

    compiled = _compile(
        heads_first, _sds(one_chip, (1, heads, queries, 128)), leaf, leaf,
        _sds(one_chip, (), jnp.int32))
    text = compiled.as_text()
    assert "chunk_attn" in text
    moved = _relaid(text, _moved(text, (
        "[1,%d,%d,128]" % (rows, kvh), "[1,%d,%d,128]" % (heads, queries),
        "[1,%d,%d,128]" % (queries, heads))))
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


EXPERT_SHAPES = {
    # rows (lanes or a chunk's tokens x picks), experts held, d, width
    "kimi-linear-decode": (256, 64, 2304, 1024),
    "kimi-linear-chunk-8192": (65536, 64, 2304, 1024),
    "xing4-decode": (128, 64, 3584, 1024),
    "xing4-chunk-2048": (8192, 64, 3584, 1024),
    "kimi-k2-decode": (256, 12, 7168, 2048),
    "kimi-k2-chunk-4096": (32768, 12, 7168, 2048),
    "nemotron-decode": (384, 64, 2688, 1920),
    "nemotron-chunk-8192": (49152, 64, 2688, 1920),
    "24-lanes-of-8-picks": (192, 64, 2304, 1024),
    "3-lanes-of-8-picks": (24, 12, 7168, 2048),
}


@pytest.mark.parametrize("m,held,d,width", EXPERT_SHAPES.values(),
                         ids=EXPERT_SHAPES.keys())
def test_grouped_matmul(one_chip, m, held, d, width):
    """The routed experts' grouped matmul at the three expert cells' real
    shapes, decode's row and a prefill chunk, in both orientations as
    _expert_ffn chains them (in, gate, out): the kernel is in the
    program three times and no copy or transpose of a weight stands in
    front of it (the weights go in as the parameters they are)."""
    from mxnet_tpu.kernels.grouped_matmul import grouped_matmul

    def experts(rows, w1, w3, w2, sizes):
        h = jax.nn.silu(grouped_matmul(rows, w1, sizes, interpret=False)) \
            * grouped_matmul(rows, w3, sizes, interpret=False)
        return grouped_matmul(h, w2, sizes, interpret=False)

    compiled = _compile(
        experts, _sds(one_chip, (m, d)), _sds(one_chip, (held, d, width)),
        _sds(one_chip, (held, d, width)), _sds(one_chip, (held, width, d)),
        _sds(one_chip, (held,), jnp.int32))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "moe_gmm" in text and "ragged-dot" not in text
    moved = _moved(text, ("bf16[%d,%d,%d]" % (held, d, width),
                          "bf16[%d,%d,%d]" % (held, width, d)))
    assert not moved, moved
    # nothing but the hidden rows between the calls: no copy of a weight
    # among the temporaries either
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * m * max(d, width) * 2 + 2 ** 20


@pytest.mark.parametrize("name,runner", [
    ("cerebras-gpt-1.3b", "lm_common"), ("jamba2-3b", "serve_jamba"),
    ("kimi-linear-48b-a3b", "serve_kimi_linear")])
def test_a_fresh_lanes_row_is_broadcasts_not_a_literal(one_chip, name,
                                                       runner):
    """The one program an admission's zeroed row comes from
    (serving._jitted_fresh_row), at the three served configurations'
    sizes: a row of up to 0.4 GB must not be folded into the executable,
    which the persistent cache holds beside the depth-sized programs."""
    import json
    import os
    from mxnet_tpu.models.serving import _jitted_fresh_row
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           name + ".json")) as f:
        cfg = importlib.import_module(
            "chipbench.runners." + runner).program_config(json.load(f))
    body = _jitted_fresh_row(cfg).__wrapped__
    row = jax.eval_shape(body)
    compiled = jax.jit(body, out_shardings=jax.tree.map(
        lambda _: one_chip, row)).lower().compile()
    mem = compiled.memory_analysis()
    row_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(row))
    assert row_bytes > 10 * 2 ** 20
    assert mem.generated_code_size_in_bytes < 2 ** 20
    assert row_bytes <= mem.output_size_in_bytes < 1.05 * row_bytes
    assert mem.temp_size_in_bytes == 0 and mem.argument_size_in_bytes == 0
    text = compiled.as_text()
    assert text.count(" broadcast(") == len(jax.tree.leaves(row))
    assert len(text) < 2 ** 17           # no literal spelled out


# --------------------------------------------- the SmallThinker cell ---

def _cell_sides(one_chip, name, ref, runner):
    """(program config, parameter shapes) of a served configuration of
    chipbench/configs on the described chip."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    flat = {leaf: _sds(one_chip, shape)
            for leaf, shape, _ in ref.leaf_specs(config)}
    return runner.program_config(config), ref.as_tree(flat, config)


@pytest.fixture(scope="module")
def smallthinker(one_chip):
    """The cell smallthinker-21b-serve-docchat32's sides."""
    from chipbench.reference import smallthinker as ref
    from chipbench.runners import serve_smallthinker
    return _cell_sides(one_chip, "smallthinker-21b-a3b", ref,
                       serve_smallthinker)


@pytest.fixture(scope="module")
def cerebras(one_chip):
    """The cell cerebras-gpt-1.3b-serve-closed24's sides."""
    from chipbench.reference import cerebras_gpt as ref
    from chipbench.runners import lm_common
    return _cell_sides(one_chip, "cerebras-gpt-1.3b", ref, lm_common)


def _on(one_chip, tree):
    return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)


def _decode_round(one_chip, cfg, params, lanes):
    """The batcher's one decode program compiled with the lanes donated,
    as the batcher runs it."""
    from mxnet_tpu.models import serving, transformer as tf
    cache = _on(one_chip, jax.eval_shape(lambda: tf.init_cache(cfg, lanes)))
    fn = serving._jitted_pipeline_chunk(cfg, True, 1.0, None, None, 1, False)
    lanes_i32 = _sds(one_chip, (lanes,), jnp.int32)
    return fn.lower(params, cache, None, lanes_i32, lanes_i32,
                    _sds(one_chip, (lanes, 2), jnp.uint32)).compile()


@pytest.fixture(scope="module")
def smallthinker_round(one_chip, smallthinker):
    """The SmallThinker cell's decode round, compiled once for the tests
    that read it."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return _decode_round(one_chip, *smallthinker, 32)


def test_smallthinker_decode_round(smallthinker_round):
    """The batcher's one decode program at the cell's shape, 32 lanes of
    2 full layers x 16,384 rows and 6 rings x 4,096, the cache donated:
    every store lands in place and every contraction reads the leaf as
    it lies (the chip keeps a leaf `[B, T, 4, 128]` in tiles of one
    position's 4 heads; the contraction's fusion takes them in its own
    order as it reads, a `copy` INSIDE the fusion and not an array), so
    no copy of a leaf is ever made: the lanes are aliased to the result
    and the temporaries stay under 64 MB (14 MB when written: the score
    planes and the experts' rows), where one ring leaf alone is 134 MB
    and a full layer's 537 MB, beside 11.69 GB of weights and lanes; the
    grouped matmul is the kernel."""
    compiled = smallthinker_round
    text = compiled.as_text()
    assert "moe_gmm" in text and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    print("smallthinker decode round: temporaries %d bytes, arguments %d"
          % (mem.temp_size_in_bytes, mem.argument_size_in_bytes))
    assert mem.temp_size_in_bytes < 64 * 2 ** 20
    assert 11.6e9 < mem.argument_size_in_bytes < 11.8e9
    assert mem.alias_size_in_bytes > 3.7e9          # the lanes, in place


def test_cerebras_decode_round_reads_its_rows_in_place(one_chip, cerebras,
                                                       monkeypatch):
    """24 lanes of 24 layers x 2,048 rows of 16 heads: every layer's
    contraction is the kernel kv_decode behind a store that lands in
    place, and no copy or transpose of a leaf (201 MB) stands between
    them; the temporaries stay under 32 MiB beside 12.3 GB of weights
    and lanes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = cerebras
    compiled = _decode_round(one_chip, cfg, params, 24)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 24
    assert "kv_decode" in text
    moved = _moved(text, ("[24,2048,16,128]",))
    assert not moved, moved
    mem = compiled.memory_analysis()
    print("cerebras decode round: temporaries %d bytes, arguments %d"
          % (mem.temp_size_in_bytes, mem.argument_size_in_bytes))
    assert mem.temp_size_in_bytes < 32 * 2 ** 20
    assert mem.alias_size_in_bytes > 9.6e9          # the lanes, in place


def test_cerebras_admission_holds_no_score_plane(one_chip, cerebras,
                                                 monkeypatch):
    """An admission of the bucket of 1,024 against a one-lane row, as
    `_jitted_prefill_chunk_row` jits it: every layer's chunk contraction
    is the kernel chunk_attn (24 calls), the float32 score plane
    [1, 1024, 16, 1, 2048] that three fusions a layer wrote and read is
    nowhere in the compiled program, the queries reach the kernel in the
    order the projection's matmul leaves them and the rows as the store
    leaves them (no copy or transpose of an array of their sizes but
    XLA's own moves between memory spaces), and the temporaries fall
    from 331 MB to 200."""
    from mxnet_tpu.models import transformer as tf
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = cerebras
    width = 1024
    row = _on(one_chip, jax.eval_shape(lambda: tf.init_cache(cfg, 1)))
    compiled = jax.jit(
        lambda p, c, t, s, r: tf.prefill_chunk(p, c, t, s, cfg,
                                               logits_row=r)).lower(
        params, row, _sds(one_chip, (1, width), jnp.int32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 24
    assert "chunk_attn" in text
    assert "f32[1,%d,16,1,2048]" % width not in text
    moved = _relaid(text, _moved(text, (
        "[1,2048,16,128]", "[1,%d,16,128]" % width,
        "[1,16,%d,128]" % width)))
    assert not moved, moved
    mem = compiled.memory_analysis()
    print("cerebras admission of %d: temporaries %d bytes"
          % (width, mem.temp_size_in_bytes))
    assert mem.temp_size_in_bytes < 0.25e9


def test_smallthinker_decode_round_reads_its_rows_in_place(
        smallthinker_round):
    """The two full layers' contractions are the kernel kv_decode, with
    no copy or transpose of a full layer's leaf (537 MB) anywhere, not
    even inside a fusion (the XLA text's four fusions had one each, in
    their own reading order), and the temporaries under 32 MiB (12 MB
    when written). The six rings keep the XLA text and with it the
    `copy` inside each of their fusions
    (test_smallthinker_decode_round)."""
    compiled = smallthinker_round
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 24
    assert "kv_decode" in text and "moe_gmm" in text
    moved = _moved(text, ("[32,16384,4,128]",))
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


@pytest.mark.parametrize("width,limit", [(8192, 1.2e9), (256, 0.6e9)])
def test_smallthinker_admission_chunk(one_chip, smallthinker, monkeypatch,
                                      width, limit):
    """An admission's chunk at the cell's widest and narrowest widths
    against a one-lane row: since PR 53 the two full layers' contraction
    at 8,192 is the kernel chunk_attn (8,192 queries against 16,384 rows
    of 28 heads would be 15 GB of scores in one plane; the six window
    layers contract in XLA's blocks); 1.04 GB of temporaries when
    written, the experts' 49,152 picked rows the largest of them; a
    chunk of 256 lies under the kernel's floor and keeps the one plane,
    0.49 GB. Both fit beside 11.69 GB of weights and lanes and the row
    twice (0.23 GB) in the chip's 15.75 GB."""
    from mxnet_tpu.models import transformer as tf
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = smallthinker
    row = _on(one_chip, jax.eval_shape(lambda: tf.init_cache(cfg, 1)))
    compiled = jax.jit(
        lambda p, c, t, s, r: tf.prefill_chunk(p, c, t, s, cfg,
                                               logits_row=r)).lower(
        params, row, _sds(one_chip, (1, width), jnp.int32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "moe_gmm" in text
    assert (text.count("chunk_attn/pallas_call") >= 2) == (width >= 1024)
    mem = compiled.memory_analysis()
    print("smallthinker admission of %d: temporaries %d bytes"
          % (width, mem.temp_size_in_bytes))
    assert mem.temp_size_in_bytes < limit
    assert mem.temp_size_in_bytes + 11.69e9 + 0.24e9 < 15.75 * 2 ** 30


# ------------------------------------------- the Nemotron-3-Nano cell ---

@pytest.fixture(scope="module")
def nemotron(one_chip):
    """The cell nemotron3-nano-serve-subagent64's sides as its runner
    serves them: the experts' width of 1,856 padded to 1,920 by
    `pad_expert_width`."""
    import json
    import os
    from chipbench.reference import nemotron_h as ref
    from chipbench.runners import serve_nemotron_h as runner
    from mxnet_tpu.models import transformer as tf
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "nemotron3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    flat = {leaf: _sds(one_chip, shape)
            for leaf, shape, _ in ref.leaf_specs(config)}
    params = jax.eval_shape(
        lambda w: runner.program_sides(config, 0, w)[0], flat)
    return tf.pad_expert_width({"layers": []},
                               runner.program_config(config))[1], \
        _on(one_chip, params)


def test_nemotron_decode_round(one_chip, nemotron, monkeypatch):
    """The batcher's one decode program at the cell's shape, 64 lanes of
    6 Mamba-2 states [64, 64, 128] float32 and 2 K/V blocks x 8,192 rows,
    the lanes donated: 9.97 GB of weights and lanes, the lanes (1.89 GB)
    aliased to the result; a Mamba-2 block's update and its read are ONE
    fusion over the state (134 MB for the 64 lanes), which is never
    copied or transposed; the two attention blocks' contractions are the
    kernel kv_decode and the five expert blocks' ten grouped matmuls the
    kernel moe_gmm, no weight stack copied; 20 MB of temporaries when
    written. At the published width of 1,856 (14.5 x 128) the same
    program kept XLA's ragged dot behind a copy of every `w1` stack a
    round, 0.68 GB of temporaries (PERF.md section 6, PR 50): why the
    runner serves the stacks padded to 1,920."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = nemotron
    compiled = _decode_round(one_chip, cfg, params, 64)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 10
    assert "kv_decode" in text and "moe_gmm" in text \
        and "ragged-dot" not in text
    assert not _moved(text, ("f32[64,64,64,128]", "bf16[64,8192,2,128]",
                             "bf16[64,2688,1920]", "bf16[64,1920,2688]"))
    assert text.count("mx.ssd.step") > 0
    mem = compiled.memory_analysis()
    print("nemotron decode round: temporaries %d bytes, arguments %d"
          % (mem.temp_size_in_bytes, mem.argument_size_in_bytes))
    assert 9.9e9 < mem.argument_size_in_bytes < 10.0e9
    assert mem.alias_size_in_bytes > 1.85e9         # the lanes, in place
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("width,limit", [(8192, 1.8e9), (256, 0.4e9)])
def test_nemotron_admission_chunk(one_chip, nemotron, monkeypatch, width,
                                  limit):
    """An admission's one call at the cell's widest and narrowest buckets
    against a one-lane row: the chunked form's [64 heads, 128, 128] decay
    planes for all 64 chunks of an 8,192 bucket are 0.27 GB, the experts'
    49,152 picked rows 0.26 GB; 1.62 GB of temporaries when written, 0.29
    at 256. Both fit beside 9.97 GB of weights and lanes and the row
    twice (0.06 GB) in the chip's 15.75 GB."""
    from mxnet_tpu.models import transformer as tf
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = nemotron
    row = _on(one_chip, jax.eval_shape(lambda: tf.init_cache(cfg, 1)))
    compiled = jax.jit(
        lambda p, c, t, s, r: tf.prefill_chunk(p, c, t, s, cfg,
                                               logits_row=r)).lower(
        params, row, _sds(one_chip, (1, width), jnp.int32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "mx.ssd.chunk" in text and "mx.ssd.conv" in text \
        and "moe_gmm" in text
    # since PR 53 the two attention blocks' chunk contraction too, from
    # its floor of 1,024 queries on
    assert (text.count("chunk_attn/pallas_call") >= 2) == (width >= 1024)
    mem = compiled.memory_analysis()
    print("nemotron admission of %d: temporaries %d bytes"
          % (width, mem.temp_size_in_bytes))
    assert mem.temp_size_in_bytes < limit
    assert mem.temp_size_in_bytes + 9.97e9 + 0.06e9 < 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def resnet50_step(one_chip):
    """The two programs of the cell resnet50-gluon-train-bs128's recorded
    call, as `CachedOp` and the tape build them, compiled at batch 128
    (the cell's own; both take ~40 s here): (what the forward hands over,
    its closure's leaves, how many arrays the forward returns, the
    compiled `jit_fwd_res`, the compiled `jit__apply_vjp`)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    net.hybridize()
    with autograd.pause():                   # builds the CachedOp, small
        net(nd.NDArray(jnp.zeros((1, 3, 32, 32), jnp.bfloat16)))
    op = net._cached_op
    held = {p.name: p for p in net.collect_params().values()}

    def aval(name):
        if name in held:
            data = held[name].data()._data
            return _sds(one_chip, data.shape, data.dtype)
        return _sds(one_chip, (128, 3, 224, 224))
    args = {n: aval(n) for n in op._arg_names}
    aux = {n: aval(n) for n in op._aux_names}
    diff = tuple(n for n in op._arg_names
                 if n in held and held[n].grad_req != "null")
    call = ([args[n] for n in diff], args, aux,
            _sds(one_chip, (2,), jnp.uint32))
    fn = op._get_fn(True, diff)
    returned = jax.eval_shape(fn, *call)
    (outs, _), saved = returned
    forward = fn.lower(*call).compile()
    closure = _on(one_chip, saved.bind(call))
    backward = autograd._apply_vjp.lower(
        tuple((o.shape, o.dtype) for o in outs), closure,
        tuple(_on(one_chip, outs))).compile()
    return (saved, jax.tree.leaves(closure), len(jax.tree.leaves(returned)),
            forward, backward)


def test_resnet50_forward_hands_its_backward_the_mxu_results(
        resnet50_step):
    """ISSUE 49: the forward program of the Gluon cell returns 1 output,
    106 running statistics, the 53 convolutions' results (2.71 GB) and
    107 reductions' (a mean and a variance a batch norm, the pooled
    features: 1.3 MB) where it returned 790 buffers with 9.03 GB of saved
    leaves; no ReLU mask, no normalised activation, and no parameter as
    a freshly made copy (the 213 a pullback reads are bound on the
    host)."""
    saved, closure, outputs, forward, _ = resnet50_step
    big = [v for v in saved.saved if v.ndim == 4]
    assert len(big) == 53 and all(v.dtype == jnp.bfloat16 for v in big)
    assert len(saved.saved) == 53 + 107 and outputs == 1 + 106 + 160
    assert sum(v.size * v.dtype.itemsize for v in saved.saved
               if v.ndim != 4) < 2 * 2 ** 20
    assert 2.7e9 < saved.nbytes() < 2.9e9
    assert len(closure) - len(saved.saved) == 213
    out = forward.memory_analysis().output_size_in_bytes
    print("resnet50 jit_fwd_res: %d outputs, %d bytes" % (outputs, out))
    assert saved.nbytes() <= out < saved.nbytes() + 2 ** 20


def test_resnet50_two_steps_fit_the_chip_together(resnet50_step):
    """What lets the next forward's buffers be made while this step's
    backward runs: the backward's arguments and temporaries and one more
    forward's outputs and temporaries lie under the chip's 16.9 GB with
    room for the weights, the optimizer's state and the batch (0.4 GB)."""
    *_, forward, backward = resnet50_step
    fwd, bwd = forward.memory_analysis(), backward.memory_analysis()
    together = (bwd.argument_size_in_bytes + bwd.temp_size_in_bytes
                + bwd.output_size_in_bytes
                + fwd.output_size_in_bytes + fwd.temp_size_in_bytes)
    print("resnet50 step: backward arguments %d temporaries %d, forward "
          "outputs %d temporaries %d" % (
              bwd.argument_size_in_bytes, bwd.temp_size_in_bytes,
              fwd.output_size_in_bytes, fwd.temp_size_in_bytes))
    assert together + 0.4e9 < 16.9e9
