"""SPMD transformer language model — the multi-chip flagship.

Built TPU-first rather than ported: a pure-functional decoder LM whose
parameters and activations carry jax.sharding PartitionSpecs over the
framework mesh axes (parallel/__init__.py):

  dp — batch;  tp — heads / FFN hidden (Megatron-style);  sp — sequence
  (ring attention, parallel/ring.py);  ep — MoE experts;  pp — pipeline
  stages (stage-major layer stacking + collective-permute microbatch
  schedule in parallel/pipeline.py).

The reference framework has no transformer model family beyond attention
helper ops (src/operator/contrib/transformer.cc interleaved matmul) —
this module is the capability extension SURVEY §2.3/§5 calls for, and is
what `__graft_entry__.dryrun_multichip` compiles over an N-device mesh.

Everything here is plain JAX (jit-traceable, static shapes); bf16
matmuls with fp32 accumulation target the MXU.
"""

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import kda, ssd, ssm
from ..parallel.ring import ring_attention, ring_attention_sharded
from ..parallel.pipeline import stack_stage_params, spmd_pipeline

__all__ = ["TransformerConfig", "YarnScaling", "init_params", "forward", "loss_fn",
           "make_train_step", "param_specs", "init_cache", "decode_step",
           "make_decode_step", "generate", "shard_cache", "prefill",
           "quantize_weights_int8", "beam_search", "prefill_chunk",
           "speculative_generate", "save_checkpoint", "load_checkpoint",
           "restore_train_state", "init_paged_cache", "decode_step_paged",
           "verify_chunk", "verify_chunk_paged", "pad_expert_width"]


class YarnScaling(NamedTuple):
    """A rope-scaling record (YaRN, as the configurations that state
    `rope_scaling.type: "yarn"` mean it): a rotary pair whose wavelength
    fits the `original_max_len` positions the model first saw fewer than
    `beta_slow` times keeps its frequency divided by `factor`, one that
    fits more than `beta_fast` times keeps it whole, the pairs between
    blend linearly (_rope_table); cos and sin are scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim) and the
    softmax by mscale(factor, mscale_all_dim) ** 2, with mscale(f, m) =
    0.1 m ln f + 1. A tuple, so a configuration that holds one still
    hashes by value (_serving_jit)."""
    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    # grouped-query attention: KV heads (None = n_heads, i.e. MHA).
    # Shrinks the KV cache by n_heads/n_kv_heads — the decode-bandwidth
    # lever; the flash-decode kernel reads each cache block once per
    # GROUP of query heads
    n_kv_heads: int = None
    # a head's width in an "attention" or "window" layer (None =
    # d_model // n_heads): n_heads x attn_head_dim need not be d_model
    attn_head_dim: int = None
    n_layers: int = 2
    # each layer's mixer, a tuple of n_layers names: "attention" (the K/V
    # cache kind), "window" (the same heads attending the last
    # `attn_window` positions only, its K/V rows a ring of that many),
    # "mamba" (models/ssm.py: fixed-size recurrent state instead of K/V
    # rows), "mamba2" (models/ssd.py: the scalar-decay form, a state a
    # head), "kda" (models/kda.py: a matrix-valued state a head), "mla"
    # (latent attention: one latent row a position instead of K/V
    # heads) or "ffn" (no mixer: the block is its feed-forward alone and
    # keeps no state). None = every layer attention
    layer_kinds: tuple = None
    # False = a block holds ONE sub-layer, x + f(norm x): a block with a
    # mixer has no feed-forward (no "ln2", no "w1"..), and the
    # feed-forwards are the "ffn" blocks of layer_kinds
    mixer_ffn: bool = True
    # a "window" layer's span: a query at position i sees positions
    # i - attn_window + 1 .. i
    attn_window: int = None
    d_ff: int = 128
    # the feed-forward's form, dense or a routed expert's: "gelu" =
    # w2 gelu(w1 x); "relu2" = w2 relu(w1 x)^2; "gated_silu" =
    # w2 (silu(w1 x) * w3 x); "gated_relu" = w2 (relu(w1 x) * w3 x)
    ffn: str = "gelu"
    # routed experts: 0 = a dense FFN in every layer; E > 0 = the layers
    # from `first_dense_layers` on route each token over E experts of
    # width `d_expert` (None = d_ff) and add `n_shared_experts` every
    # token passes through. `experts_per_token` k (None = E, every
    # expert) are chosen by `expert_scoring`: "softmax" = the softmax
    # over all E as the weights; "sigmoid" = sigmoid scores, the k
    # chosen (by score + the layer's "gate_bias") renormalised to sum to
    # `expert_scale`; "softmax_topk" = the softmax over the k chosen
    # logits. `experts_held` = (first, count): the contiguous
    # range of the E this program holds weights for (None = all); it
    # routes over all E and computes its own experts' part of the result.
    # `router_input`: what the router scores, "ffn" = the feed-forward's
    # normed input, "layer" = the residual stream as the layer receives
    # it, before the mixer and un-normed
    n_experts: int = 0
    experts_per_token: int = None
    expert_scoring: str = "softmax"
    router_input: str = "ffn"
    expert_scale: float = 1.0
    experts_held: tuple = None
    d_expert: int = None
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    # False = an output head of its own ("head" [vocab, d]) instead of
    # the embedding's transpose
    tied_head: bool = True
    norm_eps: float = 1e-6      # every RMSNorm's epsilon
    max_len: int = 128
    dtype: object = jnp.float32
    # mesh axis names (set to None to disable an axis)
    dp_axis: str = "dp"
    tp_axis: str = "tp"
    sp_axis: str = "sp"
    ep_axis: str = "ep"
    pp_axis: str = None         # set to 'pp' to pipeline the layer stack
    num_microbatches: int = 0   # 0 = one per pipeline stage
    # positional encoding: learned absolute embeddings (the default) or
    # rotary (RoPE) applied to q/k — position-extrapolating and the
    # standard for long-context models; the learned `pos` table is
    # simply unused when rope=True
    rope: bool = False
    rope_base: float = 10000.0
    # a YarnScaling record, or None = the frequencies as the base gives
    # them. Only latent attention reads it (an "attention" layer beside
    # one is refused: its softmax has no place for the record's scale)
    rope_scaling: YarnScaling = None
    # "none" = no positional encoding anywhere (hybrid models whose
    # state-space layers carry the order): no `pos` table, no rotation.
    # Left None, `rope` says which of the other two it is
    positions: str = None
    # under `rope`, which "attention" and "window" layers rotate: a
    # tuple of n_layers flags (None = every one); a layer whose flag is
    # off attends without positional encoding
    rope_layers: tuple = None
    # Mamba mixer sizes: states a channel, conv taps, channels as a
    # multiple of d_model, rank of the step-size projection (None =
    # ceil(d_model / 16), the family's rule)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = None
    # Mamba-2 mixer sizes: heads (None = n_heads), a head's channels
    # (None = 2 d_model / heads, the family's expansion), states a head,
    # groups of heads that share one B and one C, conv taps, positions a
    # chunk of the sequence form
    ssd_heads: int = None
    ssd_head_dim: int = None
    ssd_state: int = 128
    ssd_groups: int = 1
    ssd_conv: int = 4
    ssd_chunk: int = 128
    # KDA mixer sizes: heads (None = n_heads), the size of a head's keys
    # and values and of the gates' inner projection (None = d_model /
    # n_heads), conv taps
    kda_heads: int = None
    kda_head_dim: int = None
    kda_conv: int = 4
    # latent attention ("mla") sizes: the rank of the K/V latent, a
    # head's key part up-projected from it, its key part shared by all
    # heads (cached beside the latent; with `rope` it and the query's
    # part against it are rotated by position, the key's BEFORE it is
    # stored), a head's values; `mla_q_rank` = the rank of a latent the
    # query is projected through as well (None = one direct projection)
    mla_rank: int = None
    mla_nope_dim: int = None
    mla_rope_dim: int = None
    mla_v_dim: int = None
    mla_q_rank: int = None
    # the residual stream's width in streams: None = one stream and the
    # frame x + f(norm(x)); n = manifold-constrained hyper-connections
    # (_hyper_connect): n streams a token, every mixer and every FFN
    # reads a learned mix of them and writes back through a doubly
    # stochastic n x n matrix made by `hc_sinkhorn_iters` Sinkhorn-Knopp
    # iterations (each divisor plus `hc_eps`) from logits clipped to
    # [`hc_clamp_min`, `hc_clamp_max`]
    hc_mult: int = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    use_ring_attention: bool = True
    # decode through kernels/flash_attention.py flash_decode, and the
    # per-shard block compute inside ring attention through its carry
    # kernel (ring shards must divide the kernel's blocks). The
    # single-device causal attention of training and prefill does not
    # ask: it takes the flash kernels by its shapes
    # (causal_attention_blocks)
    use_flash_kernel: bool = False
    # activation recompute: checkpoint each transformer layer so backward
    # rematerializes its activations instead of storing them (the
    # reference's MXNET_BACKWARD_DO_MIRROR, src/nnvm/gradient.cc:285,
    # applied at the idiomatic per-layer granularity)
    remat_layers: bool = False
    # serving: int8 KV cache with per-(batch, position, head) scales —
    # halves cache HBM, doubling the slot count or context a chip can
    # hold, and the decode attention stays int8 end to end on the MXU
    # (scales applied outside the contractions; v-scales fold into the
    # softmax probabilities). Decode takes the dense grouped path —
    # the flash kernel reads full-precision caches. ~0.5-1% relative
    # error on attention outputs (tested); weight-only int8
    # (quantize_weights_int8) composes independently.
    kv_cache_int8: bool = False


def _norm_shape(cfg):
    return (cfg.d_model,)


def _kvh(cfg):
    kvh = cfg.n_kv_heads or cfg.n_heads
    if cfg.n_heads % kvh:
        raise ValueError(
            "n_heads=%d must be a multiple of n_kv_heads=%d"
            % (cfg.n_heads, kvh))
    return kvh


# what a layer of each kind other than "attention" keeps between calls.
# Only dense lanes carry any of them: paged blocks, speculation, int8
# and a mesh refuse these kinds by name (_refuse_dense_only)
_DENSE_ONLY = {"mamba": "a state-space layer's recurrent state",
               "mamba2": "a scalar-decay state-space layer's state a head",
               "kda": "a linear-attention layer's matrix state",
               "mla": "a latent-attention layer's latent rows",
               "window": "a window layer's ring of K/V rows",
               "ffn": "a feed-forward block's state without leaves"}
# the kinds whose state is a recurrence: fixed-size, and not healed by
# position as K/V or latent rows are
_RECURRENT = ("mamba", "mamba2", "kda")


def _layer_kinds(cfg):
    """The mixer of every layer, checked."""
    kinds = cfg.layer_kinds or ("attention",) * cfg.n_layers
    if len(kinds) != cfg.n_layers \
            or set(kinds) - {"attention"} - set(_DENSE_ONLY):
        raise ValueError(
            "layer_kinds must name %d layers, each 'attention', 'window', "
            "'mamba', 'mamba2', 'kda', 'mla' or 'ffn'; got %r"
            % (cfg.n_layers, kinds))
    if cfg.hc_mult is not None and (not cfg.mixer_ffn or "ffn" in kinds):
        raise ValueError(
            "a block of one sub-layer (mixer_ffn=False, or an 'ffn' block "
            "in layer_kinds) cannot sit in a residual stream of hc_mult=%r "
            "streams: a layer's two frames wrap a mixer and a feed-forward"
            % (cfg.hc_mult,))
    return tuple(kinds)


def _head_dim(cfg):
    """A head's width in an "attention" or "window" layer."""
    return cfg.attn_head_dim or cfg.d_model // cfg.n_heads


def _window(cfg):
    """A "window" layer's span in positions, checked: such a layer
    keeps that many K/V rows a lane (fewer where max_len is), position
    p at slot p mod that many (_ring_rows)."""
    w = cfg.attn_window
    if not isinstance(w, int) or w < 1 or cfg.use_flash_kernel:
        raise ValueError(
            "a 'window' layer needs attn_window >= 1 (got %r) and no "
            "use_flash_kernel: the flash kernels have no window mask"
            % (w,))
    return w


def _layer_rope(cfg):
    """Whether each layer's "attention" or "window" mixer rotates its
    queries and keys, checked."""
    flags = cfg.rope_layers
    if flags is None:
        return (bool(cfg.rope),) * cfg.n_layers
    if not cfg.rope or len(flags) != cfg.n_layers:
        raise ValueError(
            "rope_layers=%r says which of %d layers rotate under "
            "rope=True (rope=%r)" % (flags, cfg.n_layers, cfg.rope))
    return tuple(bool(f) for f in flags)


def _recurrent(cfg):
    return any(k in _RECURRENT for k in _layer_kinds(cfg))


def _dense_only(cfg):
    """The kinds of this model that only dense lanes can carry."""
    return [k for k in _DENSE_ONLY if k in _layer_kinds(cfg)]


def _refuse_dense_only(cfg, mechanism):
    """A recurrent state is exact by construction or wrong, and a latent
    row is not K/V heads: a mechanism that cannot carry a kind says so
    instead of serving other tokens."""
    kinds = _dense_only(cfg)
    if kinds:
        raise ValueError(
            "%s cannot carry %s (layer_kinds has %r layers); serve this "
            "model through the dense cache"
            % (mechanism, _DENSE_ONLY[kinds[0]], kinds[0]))


def _learned_pos(cfg):
    """Whether a learned position table is added to the embeddings."""
    if cfg.positions not in (None, "learned", "rope", "none") \
            or (cfg.positions is not None
                and cfg.rope != (cfg.positions == "rope")):
        raise ValueError(
            "positions=%r with rope=%r: positions is 'learned', 'rope' "
            "(with rope=True) or 'none'" % (cfg.positions, cfg.rope))
    if cfg.rope_scaling is not None and (
            not cfg.rope or "attention" in _layer_kinds(cfg)):
        raise ValueError(
            "rope_scaling is read by rotating latent attention alone: it "
            "needs rope=True and no 'attention' layer (layer_kinds %r)"
            % (_layer_kinds(cfg),))
    return not cfg.rope and cfg.positions != "none"


def _mamba_state(cfg, batch):
    return ssm.init_state(cfg.ssm_expand * cfg.d_model, cfg.ssm_state,
                          cfg.ssm_conv, batch, cfg.dtype)


def _ssd_sizes(cfg):
    """(heads, a head's channels, states, groups, conv taps) of a Mamba-2
    mixer, checked."""
    h = cfg.ssd_heads or cfg.n_heads
    sizes = (h, cfg.ssd_head_dim or 2 * cfg.d_model // h, cfg.ssd_state,
             cfg.ssd_groups, cfg.ssd_conv)
    if min(sizes) < 1 or h % cfg.ssd_groups or cfg.ssd_chunk < 1:
        raise ValueError(
            "a 'mamba2' layer's (heads, head size, states, groups, taps) "
            "= %r with ssd_chunk=%r: each at least 1, and the groups "
            "divide the heads" % (sizes, cfg.ssd_chunk))
    return sizes


def _mamba2_state(cfg, batch):
    return ssd.init_state(*_ssd_sizes(cfg), batch, cfg.dtype)


def _kda_sizes(cfg):
    """(heads, head size) of a KDA mixer."""
    return (cfg.kda_heads or cfg.n_heads,
            cfg.kda_head_dim or cfg.d_model // cfg.n_heads)


def _kda_state(cfg, batch):
    return kda.init_state(*_kda_sizes(cfg), cfg.kda_conv, batch, cfg.dtype)


def _mla_sizes(cfg):
    """(latent rank, a head's up-projected key part, its shared key
    part, a head's values), checked."""
    sizes = (cfg.mla_rank, cfg.mla_nope_dim, cfg.mla_rope_dim,
             cfg.mla_v_dim)
    if None in sizes:
        raise ValueError(
            "an 'mla' layer needs mla_rank, mla_nope_dim, mla_rope_dim "
            "and mla_v_dim; got %r" % (sizes,))
    return sizes


def _experts(cfg):
    """(E routed, k a token, first expert held, experts held, their
    width), checked; None for a model without routed experts."""
    e = cfg.n_experts
    if not e:
        return None
    k = cfg.experts_per_token or e
    first, held = cfg.experts_held or (0, e)
    if cfg.expert_scoring not in ("softmax", "sigmoid", "softmax_topk") \
            or not 1 <= k <= e or first < 0 or held < 1 \
            or first + held > e:
        raise ValueError(
            "n_experts=%d with experts_per_token=%r, experts_held=%r, "
            "expert_scoring=%r: k is in 1..E, the range held lies in "
            "0..E, scoring is 'softmax', 'sigmoid' or 'softmax_topk'"
            % (e, cfg.experts_per_token, cfg.experts_held,
               cfg.expert_scoring))
    if cfg.router_input not in ("ffn", "layer") or (
            cfg.router_input == "layer" and cfg.hc_mult is not None):
        raise ValueError(
            "router_input=%r: 'ffn' or 'layer', and a router that reads "
            "the layer's input cannot sit in a residual stream of "
            "hc_mult=%r streams (which of them would it read?)"
            % (cfg.router_input, cfg.hc_mult))
    return e, k, first, held, cfg.d_expert or cfg.d_ff


def _has_ffn(cfg, i):
    """Whether block i holds a feed-forward: every one does, but the
    blocks with a mixer under mixer_ffn=False."""
    return bool(cfg.mixer_ffn) or _layer_kinds(cfg)[i] == "ffn"


def _has_experts(cfg, i):
    """Whether block i's feed-forward, where the plan gives it one,
    routes over experts or is the dense FFN."""
    return bool(cfg.n_experts) and i >= cfg.first_dense_layers \
        and _has_ffn(cfg, i)


# the gated forms of cfg.ffn, w2 (act(w1 x) * w3 x), and each one's act
_GATED = {"gated_silu": jax.nn.silu, "gated_relu": jax.nn.relu}
# the forms without a gate, w2 act(w1 x)
_UNGATED = {"gelu": jax.nn.gelu,
            "relu2": lambda h: jnp.square(jax.nn.relu(h))}


def _yarn_mscale(factor, m):
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_table(cfg, dim):
    """(frequencies float32 [dim / 2], the factor on cos and sin) of a
    rotation of `dim` features under cfg.rope_scaling, or None without a
    record (then _rope takes its frequencies from the base). YaRN: pair
    i, of frequency base^(-2i/dim), completes n(i) = original_max_len *
    f_i / 2 pi turns over the positions the model first saw; the pairs
    with n >= beta_fast keep their frequency, those with n <= beta_slow
    have it divided by `factor`, and between the two pairs' indices
    (the first rounded down, the second up) the two blend linearly."""
    ys = cfg.rope_scaling
    if ys is None:
        return None
    half = dim // 2
    freqs = cfg.rope_base ** (-np.arange(half, dtype=np.float64) / half)

    def pair_of(turns):
        return dim * np.log(ys.original_max_len / (turns * 2 * np.pi)) \
            / (2 * np.log(cfg.rope_base))

    lo = max(np.floor(pair_of(ys.beta_fast)), 0)
    hi = min(np.ceil(pair_of(ys.beta_slow)), dim - 1)
    whole = 1.0 - np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
    return ((freqs / ys.factor * (1 - whole) + freqs * whole)
            .astype(np.float32),
            float(_yarn_mscale(ys.factor, ys.mscale)
                  / _yarn_mscale(ys.factor, ys.mscale_all_dim)))


def _rope(x, positions, base, table=None):
    """Rotary position encoding on [..., T, H, Dh] (or [..., H, Dh]
    with scalar/[B] positions at decode): rotate feature pairs
    (half-split convention) by position-dependent angles. `table`
    (_rope_table) replaces the base's frequencies and scales cos/sin."""
    dh = x.shape[-1]
    if dh % 2:
        raise ValueError(
            "rope needs an even head dim, got d_model/n_heads = %d" % dh)
    half = dh // 2
    if table is None:
        freqs = (1.0 / base) ** (jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = table[0]
    ang = jnp.asarray(positions, jnp.float32)[..., None] * freqs
    if jnp.ndim(positions) >= 1:
        # positions carry a T (or batch) axis that aligns with x's -3
        # axis; insert the broadcast head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if table is not None and table[1] != 1.0:
        cos, sin = cos * table[1], sin * table[1]
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return rot.astype(x.dtype)


def _repeat_kv(x, g):
    """[.., T, KVH, D] -> [.., T, H, D] by repeating each KV head over
    its query group (training/dense paths; the decode kernel maps
    groups natively instead of materializing the repeat)."""
    return x if g == 1 else jnp.repeat(x, g, axis=2)


def param_specs(cfg):
    """PartitionSpec per parameter — Megatron-style TP, experts on ep."""
    tp, ep = cfg.tp_axis, cfg.ep_axis
    attention = {
        "wq": P(None, tp, None), "wk": P(None, tp, None),
        "wv": P(None, tp, None), "wo": P(tp, None, None),
    }
    # a Mamba mixer is replicated: no sharded path runs one yet
    mamba = {k: P(*(None,) * n) for k, n in (
        ("in_proj", 2), ("conv_w", 2), ("conv_b", 1), ("x_proj", 2),
        ("dt_norm", 1), ("b_norm", 1), ("c_norm", 1), ("dt_proj", 2),
        ("dt_bias", 1), ("A_log", 2), ("D", 1), ("out_proj", 2))}
    # so are a Mamba-2, a KDA and a latent-attention mixer
    mamba2 = {k: P(*(None,) * n) for k, n in (
        ("in_proj", 2), ("conv_w", 2), ("conv_b", 1), ("dt_bias", 1),
        ("A_log", 1), ("D", 1), ("y_norm", 1), ("out_proj", 2))}
    kda_mixer = {k: P(*(None,) * n) for k, n in (
        ("wqkv", 2), ("conv_w", 2), ("f_a", 2), ("f_b", 2), ("dt_bias", 1),
        ("A_log", 1), ("b_proj", 2), ("g_a", 2), ("g_b", 2), ("o_norm", 1),
        ("out_proj", 2))}
    mla = {k: P(*(None,) * n) for k, n in (
        (("wq_a", 2), ("q_norm", 1), ("wq_b", 3)) if cfg.mla_q_rank
        else (("wq", 3),))
        + (("wkva", 2), ("kv_norm", 1), ("wkvb", 3), ("wo", 3))}
    mixers = {"attention": attention, "window": attention, "mamba": mamba,
              "mamba2": mamba2, "kda": kda_mixer, "mla": mla}
    gated = cfg.ffn in _GATED
    dense = {"w1": P(None, tp), "w2": P(tp, None)}
    if gated:
        dense["w3"] = P(None, tp)
    # routed experts over ep, their width over tp; a shared expert is a
    # dense FFN every token passes through
    experts = {"gate": P(None, None),
               "w1": P(ep, None, tp), "w2": P(ep, tp, None)}
    if gated:
        experts["w3"] = P(ep, None, tp)
    if cfg.expert_scoring == "sigmoid":
        experts["gate_bias"] = P(None)
    if cfg.n_shared_experts:
        experts.update({"ws" + k[1:]: v for k, v in dense.items()})
    # a hyper-connection frame's leaves are replicated (only shapes read
    # them: a mesh refuses the n-stream carry, _refuse_streams)
    frames = {} if cfg.hc_mult is None else {
        "%s_%s" % (name, k): P(*(None,) * rank) for name in HC_FRAMES
        for k, rank in HC_LEAVES}

    def layer(i, kind):
        ffn = experts if _has_experts(cfg, i) else dense
        if kind == "ffn":       # a block of one sub-layer: its norm and
            return dict(ffn, ln2=P(None))               # its leaves
        if not cfg.mixer_ffn:
            return dict(mixers[kind], ln1=P(None))
        return dict(ffn, ln1=P(None), ln2=P(None), **frames, **mixers[kind])

    out = {
        "embed": P(None, None),
        "ln_f": P(None),
        "layers": [layer(i, kind)
                   for i, kind in enumerate(_layer_kinds(cfg))],
    }
    if not cfg.tied_head:
        out["head"] = P(None, None)
    if _learned_pos(cfg):
        out["pos"] = P(None, None)
    return out


# the two frames of a layer under `hc_mult` (around the mixer, around the
# FFN) and each one's leaves with their ranks: p["hc1_phi"], ...
HC_FRAMES = ("hc1", "hc2")
HC_LEAVES = (("phi", 2), ("b", 1), ("a", 1))
# a frame's initial gains (init_params), all three
HC_INIT_GAIN = 0.5


def _hc_init_biases(n):
    """A frame's initial biases [n (n + 2)], in its projection's column
    order: stream 0 is read with weight near 0.9 and written with weight
    near 1, the others with 0.1 and 0.25, and the mix starts 1.5 heavier
    on the diagonal. With HC_INIT_GAIN these put seeded weights away
    from both the identity and the uniform mix: H_res's largest entry a
    token lies in 0.4-0.9."""
    first = np.arange(n) == 0
    return np.concatenate([np.where(first, 2.0, -2.0),
                           np.where(first, 0.0, -2.0),
                           1.5 * np.eye(n).reshape(-1)])


def init_params(cfg, seed=0):
    rng = np.random.RandomState(seed)
    dt = cfg.dtype
    hd = _head_dim(cfg)

    def dense(*shape):
        scale = 1.0 / np.sqrt(shape[0] if len(shape) == 2 else cfg.d_model)
        return jnp.asarray(rng.randn(*shape) * scale, dt)

    def mamba():
        # the family's initialisation: A = -(1..N) on every channel, a
        # bias that puts softplus(dt) log-uniform in [1e-3, 1e-1], D = 1
        e, n = cfg.ssm_expand * cfg.d_model, cfg.ssm_state
        r = cfg.ssm_dt_rank or -(-cfg.d_model // 16)
        dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), e))
        return {
            "in_proj": dense(cfg.d_model, 2 * e),
            "conv_w": dense(cfg.ssm_conv, e),
            "conv_b": jnp.zeros((e,), dt),
            "x_proj": dense(e, r + 2 * n),
            "dt_norm": jnp.ones((r,), dt),
            "b_norm": jnp.ones((n,), dt),
            "c_norm": jnp.ones((n,), dt),
            "dt_proj": dense(r, e),
            "dt_bias": jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), dt),
            "A_log": jnp.asarray(np.log(np.tile(
                np.arange(1.0, n + 1)[:, None], (1, e))), dt),
            "D": jnp.ones((e,), dt),
            "out_proj": dense(e, cfg.d_model),
        }

    def mamba2():
        # the family's initialisation: A = -U(1, 16) a head, a bias that
        # puts softplus(dt) log-uniform in [1e-3, 1e-1], D = 1
        h, hp, n, g, k = _ssd_sizes(cfg)
        e, conv = h * hp, h * hp + 2 * g * n
        dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), h))
        return {
            "in_proj": dense(cfg.d_model, e + conv + h),
            "conv_w": dense(k, conv),
            "conv_b": jnp.zeros((conv,), dt),
            "dt_bias": jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), dt),
            "A_log": jnp.asarray(np.log(rng.uniform(1.0, 16.0, h)), dt),
            "D": jnp.ones((h,), dt),
            "y_norm": jnp.ones((e,), dt),
            "out_proj": dense(e, cfg.d_model),
        }

    def kda_mixer():
        # the family's initialisation: A = -U(1, 16) a head, a bias that
        # puts softplus(dt) log-uniform in [1e-3, 1e-1]; the decay's two
        # leaves stay float32
        h, dk = _kda_sizes(cfg)
        dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), h * dk))
        return {
            "wqkv": dense(cfg.d_model, 3 * h * dk),
            "conv_w": dense(cfg.kda_conv, 3 * h * dk),
            "f_a": dense(cfg.d_model, dk), "f_b": dense(dk, h * dk),
            "dt_bias": jnp.asarray(dt0 + np.log(-np.expm1(-dt0)),
                                   jnp.float32),
            "A_log": jnp.asarray(np.log(rng.uniform(1.0, 16.0, h)),
                                 jnp.float32),
            "b_proj": dense(cfg.d_model, h),
            "g_a": dense(cfg.d_model, dk), "g_b": dense(dk, h * dk),
            "o_norm": jnp.ones((dk,), dt),
            "out_proj": dense(h * dk, cfg.d_model),
        }

    def mla():
        r, n, e, v = _mla_sizes(cfg)
        rq = cfg.mla_q_rank
        query = {"wq": dense(cfg.d_model, cfg.n_heads, n + e)} if not rq \
            else {"wq_a": dense(cfg.d_model, rq),
                  "q_norm": jnp.ones((rq,), dt),
                  "wq_b": jnp.asarray(
                      rng.randn(rq, cfg.n_heads, n + e) / np.sqrt(rq), dt)}
        return {
            **query,
            "wkva": dense(cfg.d_model, r + e),
            "kv_norm": jnp.ones((r,), dt),
            "wkvb": jnp.asarray(
                rng.randn(r, cfg.n_heads, n + v) / np.sqrt(r), dt),
            "wo": dense(cfg.n_heads, v, cfg.d_model),
        }

    def attention():
        return {
            "wq": dense(cfg.d_model, cfg.n_heads, hd),
            "wk": dense(cfg.d_model, _kvh(cfg), hd),
            "wv": dense(cfg.d_model, _kvh(cfg), hd),
            "wo": dense(cfg.n_heads, hd, cfg.d_model),
        }

    mixers = {"attention": attention, "window": attention, "mamba": mamba,
              "mamba2": mamba2, "kda": kda_mixer, "mla": mla}
    gated = cfg.ffn in _GATED

    def experts():
        _, _, _, held, f = _experts(cfg)

        def stack(fan_in, fan_out):
            return jnp.asarray(rng.randn(held, fan_in, fan_out)
                               / np.sqrt(fan_in), dt)

        p = {"gate": dense(cfg.d_model, cfg.n_experts),
             "w1": stack(cfg.d_model, f), "w2": stack(f, cfg.d_model)}
        if gated:
            p["w3"] = stack(cfg.d_model, f)
        if cfg.expert_scoring == "sigmoid":
            # the score-correction bias only orders the experts
            p["gate_bias"] = jnp.asarray(
                rng.randn(cfg.n_experts) * 0.02, jnp.float32)
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            p["ws1"] = dense(cfg.d_model, fs)
            p["ws2"] = dense(fs, cfg.d_model)
            if gated:
                p["ws3"] = dense(cfg.d_model, fs)
        return p

    def frame():
        n, cols = _hc_sizes(cfg)
        return {"phi": dense(n * cfg.d_model, cols),
                "b": jnp.asarray(_hc_init_biases(n), jnp.float32),
                "a": jnp.full((3,), HC_INIT_GAIN, jnp.float32)}

    def layer(i, kind):
        # a block of one sub-layer has that sub-layer's norm and leaves
        mixer, ffn = kind != "ffn", _has_ffn(cfg, i)
        p = {}
        if mixer:
            p["ln1"] = jnp.ones(_norm_shape(cfg), dt)
        if ffn:
            p["ln2"] = jnp.ones(_norm_shape(cfg), dt)
        if cfg.hc_mult is not None:
            p.update({"%s_%s" % (name, k): v for name in HC_FRAMES
                      for k, v in frame().items()})
        if mixer:
            p.update(mixers[kind]())
        if _has_experts(cfg, i):
            p.update(experts())
        elif ffn:
            p["w1"] = dense(cfg.d_model, cfg.d_ff)
            p["w2"] = dense(cfg.d_ff, cfg.d_model)
            if gated:
                p["w3"] = dense(cfg.d_model, cfg.d_ff)
        return p

    out = {
        "embed": jnp.asarray(rng.randn(cfg.vocab_size, cfg.d_model) * 0.02,
                             dt),
        "ln_f": jnp.ones(_norm_shape(cfg), dt),
        "layers": [layer(i, kind)
                   for i, kind in enumerate(_layer_kinds(cfg))],
    }
    if not cfg.tied_head:
        out["head"] = jnp.asarray(
            rng.randn(cfg.vocab_size, cfg.d_model) * 0.02, dt)
    if _learned_pos(cfg):
        # rope models carry no learned position table — at long-context
        # scale it would be dead HBM (+ momentum + checkpoint bloat)
        out["pos"] = jnp.asarray(
            rng.randn(cfg.max_len, cfg.d_model) * 0.02, dt)
    return out


def shard_params(params, cfg, mesh):
    """device_put every param with its PartitionSpec. Quantized trees
    (quantize_weights_int8) shard too: the int8 payload takes the
    weight's spec, its scale/dt sidecars replicate (scales are shared
    along the leading axis, which no spec here partitions alone)."""
    _refuse_dense_only(cfg, "mesh-sharded parameters (shard_params)")
    _refuse_streams(cfg, "mesh-sharded parameters (shard_params)")
    specs = param_specs(cfg)
    if cfg.tp_axis and cfg.tp_axis in mesh.shape:
        tp_size = mesh.shape[cfg.tp_axis]
        if _kvh(cfg) % tp_size:
            raise ValueError(
                "tp axis of size %d cannot shard %d KV heads "
                "(n_kv_heads must be a multiple of the tp width; "
                "lower tp, raise n_kv_heads, or replicate KV by "
                "setting tp_axis=None)" % (tp_size, _kvh(cfg)))

    def place(x, s):
        if _is_q8(x):
            return {"q8": jax.device_put(x["q8"], NamedSharding(mesh, s)),
                    "scale": jax.device_put(
                        x["scale"], NamedSharding(mesh, P())),
                    "dt": x["dt"]}
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree.map(place, params, specs,
                        is_leaf=lambda x: isinstance(x, P) or _is_q8(x))


def _rms_norm(x, g, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def _head(params, cfg):
    """The output projection [vocab, d]: the embedding's transpose, or
    the model's own "head"."""
    return params["embed" if cfg.tied_head else "head"]


def _qkv(x, p):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])   # KVH heads under GQA
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    return q, k, v


def _paged_pallas_requested():
    """MXNET_PAGED_DECODE_PALLAS=1 routes decode_step_paged /
    verify_chunk_paged through the batched-lane Pallas megakernel
    (kernels/paged_decode.py) instead of the fused-gather dense
    contraction. Read at trace time through _fastenv (sub-microsecond,
    monkeypatch-safe) and folded into the _serving_jit key, so an A/B
    harness can flip the flag between arms without stale programs."""
    from .. import _fastenv
    return _fastenv.get("MXNET_PAGED_DECODE_PALLAS", "0") not in (
        "0", "", "false", "False", None)


def causal_attention_blocks(q, k, v, window=None, mesh=None):
    """(block_q, block_k) where causal self-attention over q, k, v
    [B, T, H, D] (arrays, or their shapes and dtypes) runs
    kernels/flash_attention.py's kernels, forward and backward, by what
    the call holds: None, and the XLA text stays, for a window layer, for
    the mesh-sharded forward (GSPMD cannot partition a Pallas call),
    for q, k, v of more than one shape or dtype (latent self-attention:
    192-wide keys beside 128-wide values) and for the shapes
    kernels.flash_attention.flash_blocks has no blocks for (heads no
    multiple of 128 wide: toy widths; a T under its crossover, where the
    score plane is small, or one no block divides). _causal_attention
    counts its calls by this rule as it is traced (attn.causal_kernel /
    attn.causal_reference)."""
    if window is not None or mesh is not None \
            or not q.shape == k.shape == v.shape \
            or not q.dtype == k.dtype == v.dtype:
        return None
    from ..kernels.flash_attention import flash_blocks
    return flash_blocks(q.shape[1], q.shape[3], q.dtype.itemsize)


def _causal_attention(q, k, v, out_dtype, norm=None, window=None,
                      mesh=None):
    """Single-device causal attention over [B, T, H, D], shared by the
    training forward and prefill: the flash kernels (no [B, H, T, T]
    plane written, saved or read) where causal_attention_blocks has
    blocks for the call, else the dense masked softmax. `norm` is what
    the scores are divided by (None = sqrt(D)); `window`: a "window"
    layer's span, a query seeing that many positions up to its own;
    `mesh`: the mesh-sharded forward's."""
    from ..observability import core as _obs
    blocks = causal_attention_blocks(q, k, v, window, mesh)
    _obs.counter("attn.causal_kernel" if blocks
                 else "attn.causal_reference").add(1)
    if blocks:
        from ..kernels import flash_attention
        return flash_attention(
            q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
            scale=None if norm is None else 1.0 / norm).astype(out_dtype)
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / (np.sqrt(q.shape[-1]) if norm is None else norm)
    mask = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((T, T), bool), -window)
    s = jnp.where(mask[None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a,
                      v.astype(a.dtype)).astype(out_dtype)


def _attention(x, p, cfg, mesh, manual_sp=False, rotate=True, window=None):
    q, k, v = _qkv(x, p)
    if cfg.rope and rotate:
        T = x.shape[1]
        if manual_sp:
            # local shard inside shard_map: global positions start at
            # this device's sequence offset
            start = jax.lax.axis_index(cfg.sp_axis) * T
        else:
            start = 0
        positions = start + jnp.arange(T)
        q = _rope(q, positions, cfg.rope_base)
        k = _rope(k, positions, cfg.rope_base)
    # training paths attend with the repeated view; the MXU cost is the
    # same and every path below assumes matching head counts
    g = cfg.n_heads // _kvh(cfg)
    k, v = _repeat_kv(k, g), _repeat_kv(v, g)
    if manual_sp:
        # already inside a shard_map manual over sp (pipeline stage
        # body). The Pallas path only engages on real TPU: interpret-
        # mode pallas cannot run under this partially-manual shard_map
        # (see ring_attention_sharded); numerics are identical either way
        o = ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=True,
                           use_flash_kernel=cfg.use_flash_kernel
                           and jax.default_backend() == "tpu")
    elif mesh is not None and cfg.use_ring_attention and cfg.sp_axis:
        o = ring_attention_sharded(q, k, v, mesh, axis_name=cfg.sp_axis,
                                   causal=True,
                                   use_flash_kernel=cfg.use_flash_kernel)
    else:
        o = _causal_attention(q, k, v, x.dtype, window=window, mesh=mesh)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"])


def _mlp(x, w1, w2, w3, cfg):
    """The dense feed-forward on x [B, T, d], in cfg.ffn's form."""
    h = jnp.einsum("btd,df->btf", x, w1)
    if cfg.ffn in _GATED:
        h = _GATED[cfg.ffn](h) * jnp.einsum("btd,df->btf", x, w3)
    else:
        h = _UNGATED[cfg.ffn](h)
    return jnp.einsum("btf,fd->btd", h, w2)


def _ffn(x, p, cfg, loads=None, mesh=None, route_from=None):
    """A layer's feed-forward on x [B, T, d]: the dense form, or, for a
    layer with a router ("gate"), its routed experts (_expert_ffn;
    `route_from`: what the router scores where that is not x)."""
    if cfg.ffn not in _UNGATED and cfg.ffn not in _GATED:
        raise ValueError("ffn=%r: 'gelu', 'relu2', 'gated_silu' or "
                         "'gated_relu'" % (cfg.ffn,))
    if "gate" in p:
        if mesh is None and route_from is None:
            return _expert_ffn(x, p, cfg, loads)
        # the mesh-sharded forward's arm (GSPMD partitions the experts),
        # or a router that reads the layer's input
        return _expert_ffn(x, p, cfg, loads, mesh, route_from)
    return _mlp(x, p["w1"], p["w2"], p.get("w3"), cfg)


# what a decode round counts of its routing, summed over the expert
# layers: token-expert picks routed, those on experts held here, held
# experts with at least one token, the busiest held expert's tokens, the
# experts held, the expert layers. The serving programs return them in
# this order (moe_stats) and the batcher adds each to the counter
# moe.<name>
MOE_STATS = ("picks", "picks_here", "experts_touched", "load_max",
             "experts_held", "layers")


def moe_stats(loads, tokens, cfg):
    """int32 [len(MOE_STATS)] from the expert layers' per-expert loads
    (what _expert_ffn appended to `loads`) for `tokens` routed tokens."""
    load = jnp.stack(loads)                              # [layers, held]
    return jnp.stack([
        jnp.int32(tokens * _experts(cfg)[1] * load.shape[0]), jnp.sum(load),
        jnp.sum(load > 0), jnp.sum(jnp.max(load, axis=1)),
        jnp.int32(load.size), jnp.int32(load.shape[0])]).astype(jnp.int32)


def pad_expert_width(params, cfg):
    """(params, cfg) with every routed expert's hidden width padded by
    zero units to a whole number of the chip's 128 lanes: zero columns
    behind "w1" (and "w3"), zero rows behind "w2", cfg.d_expert the
    padded width. A hidden unit whose "w1" column is zero reads
    act(0) = 0 in every form of cfg.ffn (relu(0)^2, gelu(0),
    act(0) * w3 x) and its zero "w2" row adds nothing: the same
    function, at a width where kernels/grouped_matmul.py has a block and
    the chip keeps a stack in the order a matmul reads it (1,856 is
    14.5 x 128: XLA's ragged dot ran such a stack at a ninth of the HBM
    peak behind a copy of it every round, PERF.md, PR 50; 1,920 costs
    3.4% more bytes). For serving: weights are prepared once, as by
    quantize_weights_int8. A width that 128 divides and a dense model
    come back as they are. The layers are padded ONE AT A TIME, each
    waited for, and the list `params["layers"]` is refilled in place, so
    a caller that keeps no other reference to the old stacks never holds
    both (0.64 GB a stack at 64 x 2,688 x 1,856; enqueued all at once the
    ten pads kept every old stack alive until they ran: 15.5 GB in use
    at the peak where 9.97 are held, PERF.md, PR 50)."""
    if not cfg.n_experts:
        return params, cfg
    import dataclasses
    f = _experts(cfg)[4]
    pad = -f % 128
    if pad:
        grow = {"w1": 2, "w3": 2, "w2": 1}          # the hidden axis
        for i, layer in enumerate(params["layers"]):
            if "gate" in layer:
                params["layers"][i] = jax.block_until_ready({
                    name: jnp.pad(x, [(0, pad * (axis == grow[name]))
                                      for axis in range(3)])
                    if name in grow else x for name, x in layer.items()})
                del layer
    return params, dataclasses.replace(cfg, d_expert=f + pad)


def expert_matmuls(params, cfg, rows):
    """Of the grouped matmuls ONE pass of `rows` token rows makes
    through the expert layers of `params`: (those that run
    kernels/grouped_matmul.py's kernel, those that keep
    jax.lax.ragged_dot), by the call's own rule from the shapes
    (grouped_tiles). serving.py adds them to the counters
    moe.grouped_kernel / moe.grouped_reference."""
    from ..kernels.grouped_matmul import grouped_tiles
    if not cfg.n_experts:
        return 0, 0
    m = rows * _experts(cfg)[1]
    kernel = [w.dtype == params["embed"].dtype and grouped_tiles(
                  m, w.shape[1], w.shape[2], w.dtype.itemsize) is not None
              for p in params["layers"] if "gate" in p
              for w in (p[name] for name in ("w1", "w3", "w2") if name in p)]
    return sum(kernel), len(kernel) - sum(kernel)


def _expert_ffn(x, p, cfg, loads, mesh=None, route_from=None):
    """Routed experts on x [B, T, d]: every token is scored (from its
    row of x, or of `route_from` [B, T, d] where the router reads
    something else: cfg.router_input) over all E
    experts and picks k of them; the picks that fall on the experts held
    here are sorted by expert and run as ONE grouped matmul a weight
    (kernels/grouped_matmul.py: each expert sees only its own tokens, no
    capacity, no dropped token, and an expert without a pick is not
    read; with `mesh`, where GSPMD partitions the experts over `ep`, as
    jax.lax.ragged_dot); the picks on experts held elsewhere
    add nothing here, though they keep their share of the renormalised
    weights. A shared expert is a dense FFN added for every token. With
    `loads` a list, the per-expert token counts [held] are appended."""
    from ..kernels.grouped_matmul import grouped_matmul
    _, k, first, held, _ = _experts(cfg)
    b, t, d = x.shape
    rows = x.reshape(b * t, d)
    with jax.named_scope("mx.moe.route"):
        logits = jnp.einsum(
            "nd,de->ne",
            rows if route_from is None else route_from.reshape(b * t, d),
            p["gate"], preferred_element_type=jnp.float32)
        if cfg.expert_scoring == "sigmoid":
            score = jax.nn.sigmoid(logits)
            _, top = jax.lax.top_k(
                score + p["gate_bias"].astype(jnp.float32), k)
            w = jnp.take_along_axis(score, top, axis=-1)
            w = cfg.expert_scale * w / jnp.sum(w, axis=-1, keepdims=True)
        elif cfg.expert_scoring == "softmax_topk":
            w, top = jax.lax.top_k(logits, k)
            w = jax.nn.softmax(w, axis=-1)
        else:
            w, top = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        # a pick's group: its expert's place among those held, or one
        # past them ("elsewhere", sorted last and in no group)
        here = (top >= first) & (top < first + held)
        group = jnp.where(here, top - first, held).reshape(-1)
        order = jnp.argsort(group)
        sizes = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32),
                        axis=0)
        if loads is not None:
            loads.append(sizes)
    with jax.named_scope("mx.moe.experts"):
        def matmul(a, weight):
            return grouped_matmul(a, weight, sizes,
                                  partitioned=mesh is not None)

        picked = rows[order // k]                        # [N * k, d]
        h = matmul(picked, p["w1"])
        if cfg.ffn in _GATED:
            h = _GATED[cfg.ffn](h) * matmul(picked, p["w3"])
        else:
            h = _UNGATED[cfg.ffn](h)
        y = matmul(h, p["w2"])
        # back in pick order; rows in no group hold nothing to read
        y = jnp.where(here.reshape(-1, 1), y[jnp.argsort(order)], 0)
        y = jnp.einsum("nkd,nk->nd", y.reshape(b * t, k, d),
                       jnp.where(here, w, 0.0)).astype(x.dtype)
    y = y.reshape(b, t, d)
    if "ws1" in p:
        with jax.named_scope("mx.moe.shared"):
            y = y + _mlp(x, p["ws1"], p["ws2"], p.get("ws3"), cfg)
    return y


def _pp_size(cfg, mesh):
    if mesh is None or not cfg.pp_axis:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(cfg.pp_axis, 1)


def _layer(x, p, kind, cfg, mix, state=None, loads=None, mesh=None,
           rotate=True):
    """One transformer block, the residual frame every entry point
    runs: x + mix(ln1 x), then x + ffn(ln2 x), each where the block has
    that sub-layer (an "ffn" block has no mixer, and under
    cfg.mixer_ffn=False a block with a mixer has no feed-forward: its
    parameters say which, "ln1" / "ln2"). x is [B, C, d], or
    [B, d] for decode's one row. With `cfg.hc_mult` the stream is n
    streams wide (x [n, B, C, d] / [n, B, d]) and each of the two
    sub-layers reads and writes it through _hyper_connect.
    `mix(kind, h, p, state, rotate)` is the entry point's mixer (_mixer;
    `rotate`: this layer's flag of _layer_rope) and
    returns (y, the layer's new state); returns (x, that state).
    `loads`, `mesh` (the mesh-sharded forward's): see _expert_ffn."""
    # a router placed before the mixer scores the layer's own input
    route_from = x if "gate" in p and cfg.router_input == "layer" else None

    def mixer(h):
        nonlocal state
        y, state = mix(kind, _rms_norm(h, p["ln1"], cfg.norm_eps), p, state,
                       rotate)
        return y

    def ffn(h):
        h = _rms_norm(h, p["ln2"], cfg.norm_eps)
        if h.ndim == 2:
            return _ffn(h[:, None], p, cfg, loads, mesh,
                        None if route_from is None
                        else route_from[:, None])[:, 0]
        return _ffn(h, p, cfg, loads, mesh, route_from)

    if cfg.hc_mult is None:
        if "ln1" in p:
            x = x + mixer(x)
        return (x + ffn(x) if "ln2" in p else x), state
    x = _hyper_connect(x, p, "hc1", cfg, mixer)
    return _hyper_connect(x, p, "hc2", cfg, ffn), state


# ------------------------------------------------- hyper-connections ---
# Manifold-constrained hyper-connections (Xie et al., arXiv:2512.24880,
# over Zhu et al., arXiv:2409.19606): the residual stream is n =
# cfg.hc_mult streams a token. The streams lead the array ([n, B, C, d],
# [n, B, d] for decode's row): a stream is then a whole slab, and the
# chip tiles the last two axes, which a 4 beside d would pad fourfold.

def _hc_sizes(cfg):
    """(n streams, columns of a frame's projection: n to read, n to
    write, n * n to mix), checked."""
    n = cfg.hc_mult
    if not isinstance(n, int) or n < 2 or cfg.hc_sinkhorn_iters < 1 \
            or not cfg.hc_clamp_min < cfg.hc_clamp_max:
        raise ValueError(
            "hc_mult=%r with hc_sinkhorn_iters=%r, hc_clamp_min=%r, "
            "hc_clamp_max=%r: at least 2 streams, 1 iteration and a "
            "clamp with min < max"
            % (n, cfg.hc_sinkhorn_iters, cfg.hc_clamp_min,
               cfg.hc_clamp_max))
    return n, n * (n + 2)


def _refuse_streams(cfg, mechanism):
    """What moves the residual stream between devices knows one stream a
    token: it says so instead of running the old frame."""
    if cfg.hc_mult is not None:
        raise ValueError(
            "%s cannot carry a residual stream of hc_mult=%d streams; run "
            "this model on one device" % (mechanism, cfg.hc_mult))


def _streams_in(x, cfg):
    """The embedded tokens as the stack's carry: with hc_mult, n copies
    of them, the streams first."""
    if cfg.hc_mult is None:
        return x
    return jnp.broadcast_to(x, (_hc_sizes(cfg)[0],) + x.shape)


def _streams_out(x, cfg):
    """The stack's carry as what the final norm reads: with hc_mult,
    the sum of the streams."""
    if cfg.hc_mult is None:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)


def _sinkhorn(m, iters, eps):
    """Sinkhorn-Knopp on positive m [..., n, n]: `iters` times, every
    column divided by its sum, then every row by its, each sum plus
    `eps`. Rows then sum to 1 and columns nearly."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def _hc_weights(x, phi, b, a, cfg):
    """A frame's three mixing weights from the stream x [n, ..., d], in
    float32 whatever the stream's type: (H_pre [..., n] in (0, 1), H_post
    [..., n] in (0, 2), H_res [..., n, n] doubly stochastic). The stream
    is normed over all n * d features of a token with no weight; the
    norm's scale is applied behind the projection, which commutes."""
    n, cols = _hc_sizes(cfg)
    with jax.named_scope("mx.hc.weights"):
        xf = x.astype(jnp.float32)
        scale = jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=(0, -1)) + cfg.norm_eps)
        z = jnp.einsum("n...d,ndk->...k", x,
                       phi.reshape(n, x.shape[-1], cols),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        z = z * scale[..., None] * jnp.repeat(
            a.astype(jnp.float32), np.array([n, n, n * n]),
            total_repeat_length=cols) + b.astype(jnp.float32)
        pre = jax.nn.sigmoid(z[..., :n])
        post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
        res = _sinkhorn(
            jnp.exp(jnp.clip(z[..., 2 * n:], cfg.hc_clamp_min,
                             cfg.hc_clamp_max)).reshape(
                z.shape[:-1] + (n, n)),
            cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def _hyper_connect(x, p, name, cfg, f):
    """One sub-layer in the n-stream frame: h = H_pre @ x is what `f`
    (norm + mixer, or norm + FFN) reads, and the stream becomes
    H_res @ x + H_post (x) f(h). The frame's leaves are p[name + "_phi"]
    [n * d, n * (n + 2)] (columns: read, write, mix), "_b" the biases
    behind it and "_a" its three gains. n is small and static, so the
    two products over the streams are sums of scaled slabs: one
    elementwise pass each, and no batch of n x n matmuls."""
    n = _hc_sizes(cfg)[0]
    pre, post, res = _hc_weights(x, p[name + "_phi"], p[name + "_b"],
                                 p[name + "_a"], cfg)
    streams = [x[j].astype(jnp.float32) for j in range(n)]
    with jax.named_scope("mx.hc.pre"):
        h = sum(pre[..., j, None] * streams[j]
                for j in range(n)).astype(x.dtype)
    y = f(h)
    with jax.named_scope("mx.hc.post"):
        yf = y.astype(jnp.float32)
        return jnp.stack([
            sum(res[..., i, j, None] * streams[j] for j in range(n))
            + post[..., i, None] * yf for i in range(n)]).astype(x.dtype)


def _mixer(cfg, attend, latent=None, valid_len=None, from_zero=False,
           window=None):
    """A layer's mix by its KIND, the one place a kind is decided.
    "attention" is `attend(h, p, state, rotate)`, the entry point's form
    of it, "window" is `window(h, p, state, rotate)`, its form over the
    last cfg.attn_window positions (`rotate`: whether this layer rotates,
    _layer_rope), and "mla" is `latent(h, p, state)`, its form of latent
    attention (_latent_attend). An entry point that has no form of a
    kind refuses it (_refuse_dense_only).
    "mamba", "mamba2" and "kda" keep a recurrent state ({"conv", "ssm"} /
    {"conv", "kda"}) where an attention layer keeps K/V: the step form
    for decode's one row [B, d], the sequence form for [B, C, d], which
    with `valid_len` stops after that many rows (ssm / ssd / kda
    .mixer_seq)
    and with `from_zero` starts from a zero state whatever it was handed
    (training, and a prefill at position 0)."""
    def mix(kind, h, p, state, rotate=True):
        if kind == "mamba":
            if h.ndim == 2:
                return ssm.mixer_step(h, p, state)
            if from_zero:
                state = _mamba_state(cfg, h.shape[0])
            return ssm.mixer_seq(h, p, state, valid_len)
        if kind == "mamba2":
            if h.ndim == 2:
                return ssd.mixer_step(h, p, state, cfg.norm_eps)
            if from_zero:
                state = _mamba2_state(cfg, h.shape[0])
            return ssd.mixer_seq(h, p, state, valid_len, cfg.norm_eps,
                                 cfg.ssd_chunk)
        if kind == "kda":
            if h.ndim == 2:
                return kda.mixer_step(h, p, state, cfg.norm_eps)
            if from_zero:
                state = _kda_state(cfg, h.shape[0])
            return kda.mixer_seq(h, p, state, valid_len, cfg.norm_eps)
        if kind == "mla":
            return latent(h, p, state)
        if kind == "window":
            return window(h, p, state, rotate)
        return attend(h, p, state, rotate)
    return mix


def _run_layers(x, params, state, cfg, mix, loads=None):
    """The embedded x through every layer, each with its own state;
    returns (what the final norm reads, the new states in layer
    order)."""
    new_state = []
    x = _streams_in(x, cfg)
    for kind, rotate, p, layer in zip(_layer_kinds(cfg), _layer_rope(cfg),
                                      params["layers"], state):
        x, layer = _layer(x, p, kind, cfg, mix, layer, loads, rotate=rotate)
        new_state.append(layer)
    return _streams_out(x, cfg), new_state


def forward(params, tokens, cfg, mesh=None):
    """tokens [B, T] int32 -> logits [B, T, vocab]."""
    x = params["embed"][tokens]
    if _learned_pos(cfg):
        x = x + params["pos"][: tokens.shape[1]]
    act = P(cfg.dp_axis, cfg.sp_axis, None)
    if mesh is not None:
        _refuse_dense_only(cfg, "the mesh-sharded forward (ring "
                          "attention, pipeline stages, tp)")
        _refuse_streams(cfg, "the mesh-sharded forward (ring attention, "
                        "pipeline stages over pp_axis, tp)")
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, act))
    x = _streams_in(x, cfg)
    n_stages = _pp_size(cfg, mesh)
    # ring attention runs manually over sp inside a pipeline stage
    ring = n_stages > 1 and bool(cfg.use_ring_attention and cfg.sp_axis)
    # self-attention over the fresh K/V: training keeps no state
    mix = _mixer(cfg, lambda h, p, _, rotate: (
        _attention(h, p, cfg, mesh, manual_sp=ring, rotate=rotate), None),
        _latent_attend(cfg, None, lambda layer, **rows: None,
                       _latent_self_attention(cfg)), from_zero=True,
        window=lambda h, p, _, rotate: (
            _attention(h, p, cfg, mesh, rotate=rotate,
                       window=_window(cfg)), None))
    if n_stages > 1:
        # pipeline the homogeneous layer stack over pp: stage-major
        # stacked weights, ppermute microbatch schedule; tp/ep stay auto
        def layer_fn(p, xm):
            return _layer(xm, p, "attention", cfg, mix, mesh=mesh)[0]

        if cfg.remat_layers:
            layer_fn = jax.checkpoint(layer_fn)
        if cfg.rope_layers is not None or not cfg.mixer_ffn:
            raise ValueError("pipeline stages stack one layer body: "
                             "rope_layers cannot differ by layer there, "
                             "nor blocks of one sub-layer (mixer_ffn)")
        stacked = stack_stage_params(params["layers"], n_stages)
        x = spmd_pipeline(
            layer_fn, stacked, x, mesh, axis_name=cfg.pp_axis,
            num_microbatches=cfg.num_microbatches or None,
            extra_manual_axes=(cfg.sp_axis,) if ring else (),
            microbatch_spec=P(None, None, cfg.sp_axis, None) if ring
            else P())
    else:
        def layer_body(p, xl, kind, rotate):
            xl = _layer(xl, p, kind, cfg, mix, mesh=mesh, rotate=rotate)[0]
            if mesh is not None:
                xl = jax.lax.with_sharding_constraint(
                    xl, NamedSharding(mesh, act))
            return xl

        if cfg.remat_layers:
            # save only layer boundaries; backward recomputes each
            # layer's internals (attention scores, ffn hidden) on the fly
            layer_body = jax.checkpoint(layer_body, static_argnums=(2, 3))
        for kind, rotate, p in zip(_layer_kinds(cfg), _layer_rope(cfg),
                                   params["layers"]):
            x = layer_body(p, x, kind, rotate)
    x = _rms_norm(_streams_out(x, cfg), params["ln_f"], cfg.norm_eps)
    return jnp.einsum("btd,vd->btv", x, _head(params, cfg))


def loss_fn(params, tokens, cfg, mesh=None):
    """Next-token cross entropy (mean over B, T-1)."""
    # keep the full (sp-divisible) sequence through the model; shift the
    # logits instead of the inputs
    logits = forward(params, tokens, cfg, mesh)[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# ------------------------------------------------------------- decode ---
# Autoregressive inference: a per-layer KV cache plus a T_q=1 step.
# Prefill could reuse forward(); the same step also serves prefill
# token-by-token, which keeps one compiled program for everything.
# The attention reads ride kernels/flash_attention.flash_decode on TPU
# (cache streamed through VMEM, masked by the dynamic position) and a
# dense masked einsum elsewhere — identical numerics.

def init_cache(cfg, batch):
    """Zeroed per-layer K/V caches sized to cfg.max_len. With
    cfg.kv_cache_int8, each layer holds int8 codes plus per-(batch,
    position, head) fp32 scales ("ks"/"vs") — ~half the HBM of a bf16
    cache (the fp32 scale planes add 4/head_dim of the code bytes:
    ~3% at head_dim 128, but 25% at head_dim 16 — small-head configs
    keep less than the headline half).

    A Mamba layer (cfg.layer_kinds) holds no rows but a fixed-size
    state, {"conv": [B, K-1, E], "ssm": [B, N, E] float32}, a Mamba-2
    layer {"conv": [B, K-1, E + 2GN], "ssm": [B, H, P, N] float32}, and
    a KDA layer {"conv": [B, K-1, 3*H*Dk], "kda": [B, H, Dk, Dv]
    float32}, a matrix a head; an "ffn" block's state has no leaves
    (states stay one a layer, and what moves a lane's rows moves nothing
    there): batch first like the rows, so whatever moves a lane's
    rows (the batcher's lane write, beam search's re-gather) moves its
    state the same way. A latent-attention layer holds rows of ONE
    latent a position, {"c": [B, max_len, R], "kr": [B, max_len, E]}:
    the normed K/V latent and the key part all heads share (two leaves
    because the chip tiles a leaf's last axis by 128: R + E = 576 in one
    leaf costs two copies of the whole cache a decode round). A window
    layer holds K/V rows like an attention layer's, but a RING of
    min(attn_window, max_len) of them (_ring_rows)."""
    if cfg.kv_cache_int8:
        _refuse_dense_only(cfg, "kv_cache_int8")
    states = {"mamba": _mamba_state, "mamba2": _mamba2_state,
              "kda": _kda_state, "ffn": lambda cfg, batch: {}}
    return [states[kind](cfg, batch) if kind in states else
            _kv_leaves(cfg, batch, min(_window(cfg), cfg.max_len)
                       if kind == "window" else cfg.max_len, kind)
            for kind in _layer_kinds(cfg)]


def _kv_leaves(cfg, n, t, kind="attention"):
    """An attention layer's zeroed K/V leaves [n, t, KVH, D]: rows of a
    dense cache (n lanes, t = max_len; a window layer's ring, t = its
    rows) or blocks of a paged pool (n
    blocks of t positions). Under kv_cache_int8, int8 codes plus the
    fp32 scale planes "ks"/"vs" [n, t, KVH]. An "mla" layer's leaves
    are its latent rows, "c" [n, t, R] and "kr" [n, t, E]."""
    if kind == "mla":
        r, _, e, _ = _mla_sizes(cfg)
        return {"c": jnp.zeros((n, t, r), cfg.dtype),
                "kr": jnp.zeros((n, t, e), cfg.dtype)}
    shape = (n, t, _kvh(cfg), _head_dim(cfg))
    if cfg.kv_cache_int8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:3], jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "vs": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _kv_quant(x):
    """Symmetric int8 over the last axis: x [..., D] ->
    (codes int8 [..., D], scale fp32 [...])."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _kv_dequant(q8, scale, dtype):
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _kv_store(layer, fresh, cfg, put):
    """The one store of a call's fresh rows (`fresh`: {"k", "v"}, or a
    latent layer's {"c", "kr"}) into a layer's leaves, quantizing K/V on the
    way in under kv_cache_int8 (codes, and their scales into "ks"/"vs").
    `put(leaf, arr)` is the state kind's primitive: it returns `leaf`
    with `arr` written where that kind puts it."""
    if cfg.kv_cache_int8:
        kq, ks = _kv_quant(fresh["k"])
        vq, vs = _kv_quant(fresh["v"])
        fresh = {"k": kq, "ks": ks, "v": vq, "vs": vs}
    return {name: put(layer[name], arr.astype(layer[name].dtype))
            for name, arr in fresh.items()}


# What an attention layer keeps between calls is one of two KINDS of
# state, chosen by what the caller holds (never by a flag): dense rows,
# or paged blocks behind block tables (further down, with the pool).
# Each kind is a (store, read) pair for one call's positions `where`
# and its contraction: store(layer, k=k, v=v) writes the fresh k/v
# there, and read(q, layer, k, v) hands `contract(q, view)` the stored
# layer as [B, T, KVH, D], position-ordered, so every kind feeds the
# SAME contractions (_decode_attention, _cached_attention). A latent
# layer's rows are dense rows too, store(layer, c=c, kr=kr), read by its
# own two contractions (_latent_attend). A window layer keeps a third
# kind, ring rows (_ring_rows), whatever the caller holds. The recurrent
# kinds' state is _mixer's.

def _dense_rows(cfg, where, contract):
    """Dense rows: leaves {"k", "v"[, "ks", "vs"]} [B, Tmax, KVH, ...]
    or a latent layer's {"c", "kr"} [B, Tmax, R | E], a lane a row. `where`
    is a scalar start (every lane's fresh [B, C, ...] lands on
    [start, start+C)), [B] (row i's one entry at where[i]: a scatter, or
    for a latent layer's `kr` kernels/latent_decode.py's writer, the same
    store in place) or [B, C] (row i's window at where[i, :])."""
    def store(layer, **fresh):
        if jnp.ndim(where) == 0:
            def put(leaf, arr):
                if arr.ndim < leaf.ndim:    # decode's one row is a
                    arr = arr[:, None]      # C = 1 window
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, arr, where, axis=1)
        elif where.ndim == 1:
            rows = jnp.arange(where.shape[0])

            def put(leaf, arr):
                return leaf.at[rows, where].set(arr)
            from ..kernels.latent_decode import latent_block, latent_row_store
            if "kr" in fresh and latent_block(
                    layer["kr"].shape[1]) is not None:
                # a latent layer's narrow leaf lies on the chip with its
                # positions minor, the order latent_decode reads it in;
                # the scatter wants them major and copies the whole leaf
                # there and back. The same store, in place (wherever
                # that kernel runs: _latent_decode_attention)
                kr = latent_row_store(layer["kr"], fresh.pop("kr"), where)
                return dict(_kv_store(layer, fresh, cfg, put), kr=kr)
        else:
            # out-of-bounds positions (a lane's window running past
            # max_len) are DROPPED by the scatter rather than clamped,
            # so a deep window can never corrupt an earlier,
            # still-attendable cache row
            rows = jnp.arange(where.shape[0])[:, None]

            def put(leaf, arr):
                return leaf.at[rows, where].set(arr, mode="drop")
        return _kv_store(layer, fresh, cfg, put)

    def read(q, layer, k, v):
        return contract(q, layer)

    return store, read


def _ring_rows(cfg, where, contract, valid_len=None):
    """Ring rows: a window layer's leaves {"k", "v"} [B, R, KVH, D], R =
    min(attn_window, max_len) rows a lane, position p at slot p mod R: a
    row is overwritten when it has left every later query's window.
    INVARIANT: before a call whose first position is s, slot j holds the
    newest position below s that is congruent to j mod R (a slot whose
    such position is negative holds nothing a mask admits), so every
    contraction masks by the ABSOLUTE position a slot holds, and a
    lane's next occupant never sees the last one's rows. `where` is a
    scalar start with a chunk's fresh [B, C, ...] for [start, start + C),
    of which the first `valid_len` are real (None = all; the bucket's
    padding behind them is NOT stored: in a ring it would overwrite rows
    the next query still sees), or decode's one row a lane at a scalar
    or [B] position. A chunk READS BEFORE IT STORES (_cache_attend's
    `read_first`): its early queries still see rows its late ones
    overwrite, so `read` hands `contract(q, view, first)` the ring as it
    was, unrolled into position order from position `first` = start - R,
    with the chunk's fresh rows behind it; decode's row stores first and
    `contract(q, layer)` masks the ring by position
    (_decode_attention)."""
    def store(layer, **fresh):
        rows = layer["k"].shape[1]
        if jnp.ndim(where) == 0:
            def put(leaf, arr):
                if arr.ndim < leaf.ndim:    # decode's one row
                    return jax.lax.dynamic_update_slice_in_dim(
                        leaf, arr[:, None], where % rows, axis=1)
                # the newest real position each slot holds after the
                # chunk: the chunk's row, where that is one of them
                end = where + (arr.shape[1] if valid_len is None
                               else valid_len)
                newest = end - 1 - (end - 1 - jnp.arange(rows)) % rows
                took = jnp.take(arr, jnp.clip(newest - where, 0,
                                              arr.shape[1] - 1), axis=1)
                return jnp.where((newest >= where)[None, :, None, None],
                                 took, leaf)
        else:
            lanes = jnp.arange(where.shape[0])

            def put(leaf, arr):
                return leaf.at[lanes, where % rows].set(arr)
        return _kv_store(layer, fresh, cfg, put)

    def read(q, layer, k, v):
        if q.ndim == 3:
            return contract(q, layer)
        rows = layer["k"].shape[1]
        return contract(q, {name: jnp.concatenate(
            [jnp.roll(layer[name], -(where % rows), axis=1),
             arr.astype(layer[name].dtype)], axis=1)
            for name, arr in (("k", k), ("v", v))}, where - rows)

    return store, read


def _int8_cache_attention(qg, layer_cache, mask, out_dtype):
    """The one int8 cache-read contraction (decode is its C=1 case):
    qg [B, C, KVH, G, D] fp against cache codes [B, T, KVH, D] int8.
    mask [B|1, C, T] marks attendable positions. Both products run
    int8 x int8 -> int32 on the MXU; q quantizes per call, k-scales
    multiply the scores per key position, v-scales fold into the
    re-quantized probabilities (they vary along the contraction axis,
    so they must ride the left operand). Every reader — stepped
    decode, chunked prefill, speculative verification — goes through
    THIS function, which is what keeps pool==solo and verify==decode
    bit-identical: the contract is structural, not disciplinary."""
    kq, ks = layer_cache["k"], layer_cache["ks"]
    vq, vs = layer_cache["v"], layer_cache["vs"]
    dh = qg.shape[-1]
    q8, qs = _kv_quant(qg)
    s = jnp.einsum("bckgd,btkd->bckgt", q8, kq,
                   preferred_element_type=jnp.int32).astype(jnp.float32)
    s = s * qs[..., None] * ks.transpose(0, 2, 1)[:, None, :, None, :] \
        / np.sqrt(dh)
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    a8, as_ = _kv_quant(a * vs.transpose(0, 2, 1)[:, None, :, None, :])
    o = jnp.einsum("bckgt,btkd->bckgd", a8, vq,
                   preferred_element_type=jnp.int32).astype(jnp.float32)
    return (o * as_[..., None]).astype(out_dtype)


def _cache_pspec(cfg, x):
    """Serving-cache layout rule in one place (shard_cache and beam's
    traced constraint must agree): batch over dp, heads over tp,
    sequence replicated — truncated to the leaf's rank, because int8
    scale planes are [B, T, KVH] while code planes are rank 4."""
    return P(*P(cfg.dp_axis, None, cfg.tp_axis, None)[: x.ndim])


def quantize_weights_int8(params):
    """Weight-only int8 for serving: every dense >=2-D weight becomes a
    {"q8": int8, "scale": fp32} pair with scales shared only along the
    leading (input) axis — per-output-channel for 2-D weights, finer
    than per-channel for the 3-D head-split ones; 1-D params (norms)
    stay as they are. Decode is HBM-bound on weight reads at small
    batch, so int8 storage halves (vs bf16) or quarters (vs fp32) the
    bytes per token. Under jit (make_decode_step, generate, the jitted
    prefill) XLA fuses the dequantizing convert into each weight's
    consuming matmul, so no full-precision copy is materialized; an
    EAGER decode_step call on a q8 tree dequantizes the whole tree per
    call — serve through the jitted entry points. Idempotent.
    A tree with Mamba or KDA layers is refused: A_log, the step-size
    bias and the inner norms set a recurrence's decay, and no int8 rule
    for them has been checked against a reference; so is one with
    latent-attention layers, whose up-projection decode absorbs into the
    query."""
    for leaf, kind, what in (
            ("y_norm", "mamba2", "a scalar-decay state-space layer's"),
            ("D", "mamba", "a state-space layer's"),
            ("b_proj", "kda", "a linear-attention layer's"),
            ("wkva", "mla", "a latent-attention layer's"),
            ("hc1_phi", "hc_mult", "a hyper-connection frame's")):
        if any(leaf in layer for layer in params.get("layers", ())):
            raise ValueError(
                "quantize_weights_int8 cannot carry %s parameters (the "
                "tree has %r layers)" % (what, kind))

    def q(leaf):
        if _is_q8(leaf):
            return leaf
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return leaf
        x = jnp.asarray(leaf, jnp.float32)
        amax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q8 = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        # "dt" is a zero-size carrier of the original dtype — an array
        # leaf (jit-safe) rather than a string
        return {"q8": q8, "scale": scale.astype(jnp.float32),
                "dt": jnp.zeros((0,), leaf.dtype)}
    return jax.tree.map(q, params, is_leaf=_is_q8)


def _is_q8(leaf):
    return isinstance(leaf, dict) and "q8" in leaf


def _dequantize_weights(params):
    """Inverse of quantize_weights_int8, applied INSIDE the compiled
    step — the convert fuses into each weight's consuming matmul."""
    def dq(leaf):
        if _is_q8(leaf):
            return (leaf["q8"].astype(jnp.float32) * leaf["scale"]
                    ).astype(leaf["dt"].dtype)
        return leaf
    return jax.tree.map(dq, params, is_leaf=_is_q8)


def _maybe_dequantize(params):
    return _dequantize_weights(params) \
        if any(_is_q8(l) for l in jax.tree.leaves(
            params, is_leaf=_is_q8)) else params


def shard_cache(cache, cfg, mesh):
    """Lay the KV cache out for mesh-sharded serving: batch over dp,
    heads over tp (matching the wq/wk/wv head shardings), sequence
    replicated — each device holds its heads' full cache and the
    attention needs no cross-device traffic; only wo's output
    contraction all-reduces over tp (GSPMD inserts it)."""
    _refuse_dense_only(cfg, "a mesh-sharded cache (shard_cache)")
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, _cache_pspec(cfg, x))), cache)


def _attn_scope(window):
    """The named scope of a K/V contraction, chunk or decode."""
    return jax.named_scope("mx.attn.full" if window is None
                           else "mx.attn.window")


def _decode_attention(q, layer_cache, pos, cfg, window=None):
    """q [B,H,D] vs cache [B,Tmax,KVH,D], attending positions <= pos;
    with `window`, vs a window layer's ring [B,R,KVH,D] as it is after
    the step's store (_ring_rows), attending the positions in
    (pos - window, pos] that its slots hold."""
    with _attn_scope(window):
        return _decode_contraction(q, layer_cache, pos, cfg, window)


def kv_decode_block(cfg, rows, window=None, q_dtype=None):
    """Rows a grid step of kernels/kv_decode.py where decode's
    contraction over K/V rows `rows` ([B, T, KVH, D], an array or its
    shape and dtype) runs that kernel, by what the call holds: None, and
    the XLA text stays, for a window layer's ring (its live rows are no
    prefix of the leaf's slots), an int8 cache, use_flash_kernel, a
    query of another dtype than the rows, and the shapes
    kernels.kv_decode.kv_block has no block for (a head size that is no
    multiple of 128: toy widths; a cache 128 does not divide; one so
    narrow that a block would be all of it). serving.py counts a
    dispatch's contractions by this rule (kv.decode_kernel /
    kv.decode_reference)."""
    if window is not None or cfg.kv_cache_int8 or cfg.use_flash_kernel \
            or q_dtype not in (None, rows.dtype):
        return None
    from ..kernels.kv_decode import kv_block
    _, t, kvh, d = rows.shape
    return kv_block(t, kvh, d, rows.dtype.itemsize)


def _decode_contraction(q, layer_cache, pos, cfg, window):
    cache_k, cache_v = layer_cache["k"], layer_cache["v"]
    if cfg.kv_cache_int8:
        return _decode_attention_int8(q, layer_cache, pos, cfg)
    if cfg.use_flash_kernel:
        import math
        from ..kernels import flash_decode
        # largest power-of-two block (<=128) dividing the cache length
        block_k = math.gcd(cache_k.shape[1], 128)
        return flash_decode(q, cache_k, cache_v, pos + 1,
                            block_k=block_k)
    from ..kernels.kv_decode import kv_decode, kv_decode_reference
    # pos is a scalar (all rows at the same position) or [B] (ragged
    # decode — continuous batching). One pass over each lane's rows up
    # to its position, no score plane (kernels/kv_decode.py); the same
    # sums as two XLA passes over all T rows where that kernel has no
    # block for the rows, and for a ring (kv_decode_block).
    # kernels.dense_decode_with_lse is the same contraction with a
    # deliberately different numeric profile: it emits the lse the
    # sequence-parallel shard combine needs; this serving hot loop
    # needs none. A masking/scaling fix here likely applies there too.
    if kv_decode_block(cfg, cache_k, window, q.dtype) is None:
        o = kv_decode_reference(q, cache_k, cache_v, pos, window)
    else:
        o = kv_decode(q, cache_k, cache_v, pos + 1)
    return o.astype(q.dtype)


def _decode_attention_int8(q, layer_cache, pos, cfg):
    """Decode = the C=1 case of _int8_cache_attention (nothing
    dequantized is ever materialized in HBM: the cache streams at
    int8 width, which is the point)."""
    b, h, d = q.shape
    kvh = layer_cache["k"].shape[2]
    t_pos = jnp.arange(layer_cache["k"].shape[1])
    mask = (t_pos[None, :] <= jnp.atleast_1d(pos)[:, None])[:, None, :]
    o = _int8_cache_attention(
        q.reshape(b, 1, kvh, h // kvh, d), layer_cache, mask, q.dtype)
    return o.reshape(b, h, d)


# score entries (queries x rows x heads of one sequence) up to which a
# chunk contracts through ONE float32 score plane; past it (a chunk of
# 8,192 queries against 16,384 rows of 28 heads is 15 GB of scores), and
# for every window layer, it contracts in blocks (_blocked_attention) of
# this many queries over this many rows
ATTN_PLANE_ELEMS = 1 << 28
ATTN_QUERY_BLOCK = 256
ATTN_KEY_BLOCK = 512


def _blocked_attention(q, k, v, positions, first=0, window=None):
    """q [B, C, H, D] against rows k, v [B, T, KVH, D] that lie in
    position order, row t at position first + t (`first` may be
    negative: rows below position 0 are never seen), chunk row i
    attending the rows at positions <= positions[i] ([C]) or
    positions[b, i] ([B, C]) and, with `window`, above positions - window:
    ATTN_QUERY_BLOCK queries at a time, each block over only the blocks
    of ATTN_KEY_BLOCK rows that hold a position one of its queries may
    see, with a running maximum and sum (the softmax's sums in blocks,
    as _latent_chunk_attention), grouped like _cached_attention's plane.
    Returns [B, C, H, D]."""
    b, c, h, d = q.shape
    kvh = k.shape[2]
    width = min(ATTN_KEY_BLOCK, k.shape[1])
    k, v = (jnp.pad(x, ((0, 0), (0, -x.shape[1] % width), (0, 0), (0, 0)))
            for x in (k, v))
    if positions.ndim == 1:
        positions = positions[None]

    def block(qb, pb):
        qg = qb.reshape(b, qb.shape[1], kvh, h // kvh, d)

        def part(j, carry):
            top, total, acc = carry
            kj, vj = (jax.lax.dynamic_slice_in_dim(x, j * width, width, 1)
                      for x in (k, v))
            s = jnp.einsum("bqkgd,btkd->bkgqt", qg, kj,
                           preferred_element_type=jnp.float32) / np.sqrt(d)
            at = first + j * width + jnp.arange(width)
            seen = (at <= pb[..., None]) & (at >= 0)
            if window is not None:
                seen &= pb[..., None] - at < window
            s = jnp.where(seen[:, None, None], s, -1e30)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            w = jnp.exp(s - new_top[..., None])
            keep = jnp.exp(top - new_top)
            return (new_top, keep * total + jnp.sum(w, axis=-1),
                    keep[..., None] * acc + jnp.einsum(
                        "bkgqt,btkd->bkgqd", w.astype(v.dtype), vj,
                        preferred_element_type=jnp.float32))

        # a row sees its own position, so some block gives it a real
        # maximum, before which what it summed is scaled away; a block
        # wholly outside a row's span adds 0 once it has one
        lead = (b, kvh, h // kvh, qb.shape[1])
        lo = 0 if window is None else jnp.maximum(
            (jnp.min(pb) - window + 1 - first) // width, 0)
        hi = jnp.minimum((jnp.max(pb) - first) // width + 1,
                         k.shape[1] // width)
        _, total, acc = jax.lax.fori_loop(
            lo, hi, part, (jnp.full(lead, -1e30, jnp.float32),
                           jnp.zeros(lead, jnp.float32),
                           jnp.zeros(lead + (d,), jnp.float32)))
        out = jnp.moveaxis(acc / total[..., None], 3, 1)    # [B,Q,KVH,G,D]
        return out.reshape(b, qb.shape[1], h, d).astype(q.dtype)

    size = ATTN_QUERY_BLOCK
    if c <= size:
        return block(q, positions)
    # whole blocks; the padding rows repeat the last row's position
    pad = -c % size
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ps = jnp.pad(positions, ((0, 0), (0, pad)), mode="edge")
    out = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.moveaxis(qs.reshape(b, -1, size, h, d), 1, 0),
         jnp.moveaxis(ps.reshape(ps.shape[0], -1, size), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, h, d)[:, :c]


def _attn_blocked(queries, rows, cfg):
    """Whether a chunk of `queries` against `rows` rows contracts in
    blocks: where its one score plane would pass ATTN_PLANE_ELEMS."""
    return queries * rows * cfg.n_heads > ATTN_PLANE_ELEMS


def chunk_attention_blocks(q, view, positions, window=None):
    """(block_q, block_k) where a chunk's contraction of q [B, C, H, D]
    (an array, or its shape and dtype) against a layer's view {"k", "v"}
    [B, T, KVH, D] runs kernels/chunk_attention.py's kernel, by what the
    call holds: None, and the XLA text stays, for a window layer (its
    view is the ring unrolled with the fresh rows behind it, from a
    `first` position), int8 rows, positions a lane ([B, C]: both
    verifiers, whose rows must stay bit-identical with decode's), a query
    of another dtype than the rows, and the shapes
    kernels.chunk_attention.chunk_blocks has no blocks for (heads no
    multiple of 128 wide: toy widths; a chunk under its floor of 1,024
    queries: the narrower buckets, whose planes fit VMEM, and a
    speculative round's k + 1 rows; a chunk or rows 128 does not divide). serving.py counts an admission's
    contractions by this rule (attn.chunk_kernel of attn.chunk_calls)."""
    rows = view["k"]
    if window is not None or "ks" in view or len(positions.shape) != 1 \
            or q.dtype != rows.dtype:
        return None
    from ..kernels.chunk_attention import chunk_blocks
    return chunk_blocks(q.shape[1], rows.shape[1], q.shape[2],
                        rows.shape[2], q.shape[3], rows.dtype.itemsize)


def chunk_contractions(params, cfg, row, rows):
    """Of the chunk contractions ONE prefill_chunk call of `rows` token
    rows makes against the one-lane row cache `row` (init_cache's
    layers, arrays or their shapes and dtypes), a K/V layer each: (all of
    them, those that run kernels/chunk_attention.py's kernel), by the
    call's own rule (chunk_attention_blocks). serving.py adds them to
    the counters attn.chunk_calls / attn.chunk_kernel."""
    embed = params["embed"]
    q = jax.ShapeDtypeStruct(
        (1, rows, cfg.n_heads, _head_dim(cfg)),
        (embed["dt"] if _is_q8(embed) else embed).dtype)
    at = jax.ShapeDtypeStruct((rows,), jnp.int32)
    kernel = [chunk_attention_blocks(
                  q, layer, at,
                  _window(cfg) if kind == "window" else None) is not None
              for kind, layer in zip(_layer_kinds(cfg), row)
              if kind in ("attention", "window")]
    return len(kernel), sum(kernel)


def _cached_attention(q, view, positions, cfg, out_dtype, window=None,
                      first=0):
    """The one chunk contraction against cached K/V: q [B, C, H, D]
    against a layer's view [B, T, KVH, D], chunk row i attending
    positions t <= positions[i] ([C], one window for the whole batch:
    prefill_chunk's consecutive positions from its `start`)
    or t <= positions[b, i] ([B, C], a window a lane), so stale entries
    beyond the verified stream are never read. Grouped: the KVH-head
    cache is read once per GROUP of query heads (like _decode_attention,
    no materialized repeat on the hot path). Chunked prefill and both
    verifiers read through THIS function, which is what keeps
    pool == solo and verify == decode bit-identical. Where
    chunk_attention_blocks has blocks for the call, one kernel that
    writes no score plane and reads no row behind the chunk's last
    position (kernels/chunk_attention.py). Elsewhere the XLA text: one
    score plane (_cached_plane), or for a window layer's view ([the ring
    unrolled, the fresh rows], row t at position `first` + t:
    _ring_rows) and a plane past ATTN_PLANE_ELEMS the contraction in
    blocks (_blocked_attention). All three are the same sums in another
    order."""
    with _attn_scope(window):
        if chunk_attention_blocks(q, view, positions, window):
            from ..kernels.chunk_attention import chunk_attention
            return chunk_attention(q, view["k"], view["v"],
                                   positions[0]).astype(out_dtype)
        if window is not None or (not cfg.kv_cache_int8 and _attn_blocked(
                q.shape[1], view["k"].shape[1], cfg)):
            return _blocked_attention(q, view["k"], view["v"], positions,
                                      first, window).astype(out_dtype)
        return _cached_plane(q, view, positions, cfg, out_dtype)


def _cached_plane(q, view, positions, cfg, out_dtype):
    """_cached_attention through one score plane [B, C, KVH, G, T]."""
    b, c, _, dh = q.shape
    kvh = _kvh(cfg)
    qg = q.reshape(b, c, kvh, cfg.n_heads // kvh, dh)
    t_pos = jnp.arange(view["k"].shape[1])
    if positions.ndim == 1:
        mask = (t_pos[None, :] <= positions[:, None])[None]      # [1,C,T]
    else:
        mask = t_pos[None, None, :] <= positions[:, :, None]     # [B,C,T]
    if cfg.kv_cache_int8:
        return _int8_cache_attention(qg, view, mask, out_dtype) \
            .reshape(b, c, cfg.n_heads, dh)
    ck, cv = view["k"], view["v"]
    s = jnp.einsum("bckgd,btkd->bckgt", qg, ck,
                   preferred_element_type=jnp.float32) / np.sqrt(dh)
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bckgt,btkd->bckgd", a.astype(cv.dtype), cv,
                      preferred_element_type=jnp.float32
                      ).astype(out_dtype).reshape(b, c, cfg.n_heads, dh)


def _cache_attend(cfg, where, store, read, read_first=False):
    """_mixer's `attend` (and `window`) for the entry points that keep
    K/V: project, rotate by the positions `where` (a layer whose
    `rotate` is off has no positions), `store(layer, k, v)` the fresh
    k/v, then `read(q, layer, k, v)`: attention over the stored layer
    (or, for a prefill at position 0, over the fresh k/v themselves);
    `read_first`: a chunk over ring rows reads the layer as it was, then
    stores (_ring_rows). h is [B, C, d], or decode's one row [B, d]."""
    def attend(h, p, layer, rotate=True):
        if h.ndim == 2:
            q = jnp.einsum("bd,dhk->bhk", h, p["wq"])
            k = jnp.einsum("bd,dhk->bhk", h, p["wk"])
            v = jnp.einsum("bd,dhk->bhk", h, p["wv"])
        else:
            q, k, v = _qkv(h, p)
        if cfg.rope and rotate:
            # keys are cached ROTATED: their rotation depends only on
            # their own position, so decode never re-rotates the cache
            q = _rope(q, where, cfg.rope_base)
            k = _rope(k, where, cfg.rope_base)
        if read_first:
            o = read(q, layer, k, v)
            layer = store(layer, k=k, v=v)
        else:
            layer = store(layer, k=k, v=v)
            o = read(q, layer, k, v)
        if h.ndim == 2:
            return jnp.einsum("bhk,hkd->bd", o, p["wo"]), layer
        return jnp.einsum("bchk,hkd->bcd", o, p["wo"]), layer
    return attend


# Latent attention ("mla"): q = W_q x [H, N + E], or with a query rank
# W_qb rms(W_qa x); [c, k_r] = W_kva x; the layer's row is {"c": rms(c)
# [R], "kr": k_r [E]}, all a position keeps; keys and values are
# up-projected from it, k = [W_kvb_k c, k_r shared by all heads], v =
# W_kvb_v c; scores q k^T / sqrt(N + E). A model without positions
# rotates nothing; one with `rope` rotates the query's last E features
# and k_r by the token's absolute position (_rope, under
# cfg.rope_scaling), k_r BEFORE it is stored: a row holds its position,
# and whatever moves rows must keep them at the place they were rotated
# for. A chunk contracts through the up-projected heads
# (_latent_chunk_attention); decode's one row absorbs W_kvb into the
# query and the output and contracts over the rows directly
# (_latent_decode_attention): the same sums in another order.

# queries a block of _latent_chunk_attention: a whole bucket's scores
# against max_len rows ([C, H, T] float32) would not fit beside the model;
# and stored rows a block: a block of queries contracts only with the
# blocks of rows that hold a position it may see
MLA_QUERY_BLOCK = 256
MLA_KEY_BLOCK = 512


def _latent_score_norm(cfg, width):
    """What a latent layer's scores are divided by: sqrt(N + E), less the
    scaling record's softmax scale mscale(factor, mscale_all_dim) ** 2."""
    ys = cfg.rope_scaling
    if ys is None:
        return np.sqrt(width)
    return np.sqrt(width) / _yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2


def _latent_attend(cfg, where, store, contract):
    """_mixer's `latent` for the entry points: project, rotate by the
    positions `where` (a model with `rope`; None = the call's rows sit
    at 0, 1, ...), `store(layer, c=.., kr=..)` the fresh rows, then
    `contract(q, layer, rows, p)`: the entry point's contraction over
    the stored rows (or, for training and a prefill at position 0, over
    the fresh `rows` themselves). h is [B, C, d], or decode's one row
    [B, d]."""
    def attend(h, p, layer):
        r, n, e, _ = _mla_sizes(cfg)
        if cfg.mla_q_rank:
            q = jnp.einsum("...r,rhk->...hk", _rms_norm(
                jnp.einsum("...d,dr->...r", h, p["wq_a"]), p["q_norm"],
                cfg.norm_eps), p["wq_b"])
        else:
            q = jnp.einsum("...d,dhk->...hk", h, p["wq"])
        ckr = jnp.einsum("...d,df->...f", h, p["wkva"])
        c = _rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
        kr = ckr[..., r:]
        if cfg.rope:
            with jax.named_scope("mx.mla.rope"):
                at = jnp.arange(h.shape[-2]) if where is None else where
                table = _rope_table(cfg, e)
                q = jnp.concatenate([q[..., :n], _rope(
                    q[..., n:], at, cfg.rope_base, table)], axis=-1)
                kr = _rope(kr[..., None, :], at, cfg.rope_base,
                           table)[..., 0, :]
        rows = {"c": c, "kr": kr}
        layer = store(layer, **rows)
        o = contract(q, layer, rows, p)
        return jnp.einsum("...hk,hkd->...d", o, p["wo"]), layer
    return attend


def _latent_up(rows, p, cfg):
    """rows {"c": [B, T, R], "kr": [B, T, E]} -> the heads' keys
    [B, T, H, N + E] and values [B, T, H, V]."""
    n = _mla_sizes(cfg)[1]
    with jax.named_scope("mx.mla.up"):
        kv = jnp.einsum("btr,rhk->bthk", rows["c"], p["wkvb"])
        shared = jnp.broadcast_to(
            rows["kr"][:, :, None, :],
            kv.shape[:3] + rows["kr"].shape[-1:])
        return jnp.concatenate([kv[..., :n], shared], axis=-1), kv[..., n:]


def _latent_self_attention(cfg):
    """Causal self-attention over a call's own rows (training, and a
    prefill at position 0), as _latent_attend's contraction."""
    def contract(q, layer, rows, p):
        k, v = _latent_up(rows, p, cfg)
        return _causal_attention(
            q, k, v, q.dtype, None if cfg.rope_scaling is None
            else _latent_score_norm(cfg, q.shape[-1]))
    return contract


def _latent_chunk_attention(q, rows, positions, p, cfg):
    """q [B, C, H, N + E] against the stored rows ([B, T, ...]), chunk
    row i attending t <= positions[i] ([C]) or positions[b, i] ([B, C]):
    the rows are up-projected once and the queries contract through the
    heads, MLA_QUERY_BLOCK at a time, each block over the rows up to the
    last position it sees, MLA_KEY_BLOCK at a time with a running
    maximum and sum (the softmax's sums in blocks). Returns
    [B, C, H, V]."""
    k, v = _latent_up(rows, p, cfg)
    width = min(MLA_KEY_BLOCK, k.shape[1])
    k, v = (jnp.pad(x, ((0, 0), (0, -x.shape[1] % width), (0, 0), (0, 0)))
            for x in (k, v))
    c = q.shape[1]
    if positions.ndim == 1:
        positions = positions[None]

    def block(qb, pb):
        def part(j, carry):
            top, total, acc = carry
            kj, vj = (jax.lax.dynamic_slice_in_dim(x, j * width, width, 1)
                      for x in (k, v))
            s = jnp.einsum("bqhd,bthd->bhqt", qb, kj,
                           preferred_element_type=jnp.float32) \
                / _latent_score_norm(cfg, q.shape[-1])
            seen = j * width + jnp.arange(width) <= pb[..., None]
            s = jnp.where(seen[:, None], s, -1e30)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            w = jnp.exp(s - new_top[..., None])
            keep = jnp.exp(top - new_top)
            return (new_top, keep * total + jnp.sum(w, axis=-1),
                    keep[..., None] * acc + jnp.einsum(
                        "bhqt,bthv->bhqv", w.astype(v.dtype), vj,
                        preferred_element_type=jnp.float32))

        # every row sees position 0, so the first block already gives
        # each row a real maximum; a block wholly behind a row adds 0
        lead = (q.shape[0], q.shape[2], qb.shape[1])          # [B, H, Q]
        _, total, acc = jax.lax.fori_loop(
            0, jnp.minimum(jnp.max(pb) // width + 1, k.shape[1] // width),
            part, (jnp.full(lead, -1e30, jnp.float32),
                   jnp.zeros(lead, jnp.float32),
                   jnp.zeros(lead + v.shape[-1:], jnp.float32)))
        return jnp.moveaxis(acc / total[..., None], 1, 2).astype(q.dtype)

    size = MLA_QUERY_BLOCK
    if c <= size:
        return block(q, positions)
    # whole blocks; the padding rows attend nothing that is read
    pad = -c % size
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ps = jnp.pad(positions, ((0, 0), (0, pad)))
    out = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.moveaxis(qs.reshape(q.shape[0], -1, size, *q.shape[2:]), 1, 0),
         jnp.moveaxis(ps.reshape(ps.shape[0], -1, size), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(
        (q.shape[0], -1) + out.shape[3:])[:, :c]


def _latent_decode_attention(q, rows, pos, p, cfg):
    """Decode's one row, absorbed: q [B, H, N + E] against the stored
    rows ([B, T, ...]), attending t <= pos (scalar or [B]). W_kvb's key
    half moves into the query ([H, R]) and its value half behind the
    weighted sum, so the contraction runs over the latents as they lie
    in the cache and no head's key or value is ever formed; the
    contraction itself is kernels/latent_decode.py: one pass over each
    lane's rows up to its position, no score plane (the same sums as
    two XLA passes over all T rows where the kernel cannot tile T:
    latent_block). Returns [B, H, V]."""
    from ..kernels.latent_decode import (
        latent_block, latent_decode, latent_decode_reference)
    n = _mla_sizes(cfg)[1]
    c, kr = rows["c"], rows["kr"]
    norm = _latent_score_norm(cfg, q.shape[-1])
    with jax.named_scope("mx.mla.absorbed"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :n], p["wkvb"][..., :n])
        if latent_block(c.shape[1]) is None:
            o = latent_decode_reference(
                q_lat, q[..., n:], c, kr,
                jnp.broadcast_to(pos + 1, c.shape[:1]), norm)
        else:
            o = latent_decode(q_lat, q[..., n:], c, kr, pos + 1, norm)
        return jnp.einsum("bhr,rhv->bhv", o.astype(q.dtype),
                          p["wkvb"][..., n:])


def prefill(params, cache, tokens, cfg):
    """Process the whole prompt in ONE forward pass, filling the KV
    cache for positions [0, Tp) — the serving-side complement of the
    per-token decode_step (prompt cost: one batched MXU pass instead of
    Tp tiny ones). Shares the q/k/v projection and causal-attention
    block with the training forward (_qkv/_causal_attention); ring
    (sp-sharded) attention is a training-path feature prefill does not
    engage. Returns (last_logits [B, vocab], cache)."""
    if cfg.kv_cache_int8:
        # delegate to the chunked path: its attention reads the prompt
        # rows THROUGH the quantizer, exactly as decode later will —
        # keeping solo generate() and the continuous batcher's
        # admission (which prefills via prefill_chunk) bit-identical
        return prefill_chunk(params, cache, tokens, jnp.int32(0), cfg,
                             logits_row=jnp.int32(tokens.shape[1] - 1),
                             attend_limit=int(tokens.shape[1]))
    params = _maybe_dequantize(params)
    t_p = tokens.shape[1]
    x = params["embed"][tokens]
    if _learned_pos(cfg):
        x = x + params["pos"][:t_p]
    g = cfg.n_heads // _kvh(cfg)
    store, _ = _dense_rows(cfg, 0, None)
    at = jnp.arange(t_p)

    def read(q, layer, k, v):
        # self-attention over the fresh K/V: at position 0 the rows
        # just stored are all there is to read
        rk, rv = _repeat_kv(k, g), _repeat_kv(v, g)
        if _attn_blocked(t_p, t_p, cfg) \
                and causal_attention_blocks(q, rk, rv) is None:
            with _attn_scope(None):
                return _blocked_attention(q, k, v, at)
        return _causal_attention(q, rk, rv, q.dtype)

    def window_read(q, layer, k, v):
        with _attn_scope(_window(cfg)):
            return _blocked_attention(q, k, v, at, window=_window(cfg))

    # position 0: whatever recurrent state the cache held is dropped
    mix = _mixer(cfg, _cache_attend(cfg, at, store, read),
                 _latent_attend(cfg, None, store,
                                _latent_self_attention(cfg)),
                 from_zero=True, window=_cache_attend(
                     cfg, at, _ring_rows(cfg, 0, None)[0], window_read))
    x, new_cache = _run_layers(x, params, cache, cfg, mix)
    x = _rms_norm(x[:, -1], params["ln_f"], cfg.norm_eps)
    return jnp.einsum("bd,vd->bv", x, _head(params, cfg)), new_cache


# jitted prefill per config VALUE: generate() is the latency-sensitive
# serving convenience, and re-wrapping jit per call would retrace every
# request. Content keying means a mutated config retraces (no stale
# program) and fresh-but-equal configs share one entry; the LRU bound
# keeps a long-lived server from accumulating dead compiles.
_PREFILL_JIT_CACHE = {}
_PREFILL_JIT_LIMIT = 32


def _serving_jit(kind, cfg, build):
    import dataclasses
    # the backend is part of the key: builders bake backend-dependent
    # choices (e.g. _serving_donate's donation tuple) into the wrapper,
    # so a process that pins a different backend after warming must not
    # reuse a stale wrapper
    # the paged-kernel flag is trace-time env state the builders bake
    # in, so it keys too: a bench toggling MXNET_PAGED_DECODE_PALLAS
    # between arms must get two programs, not one stale one
    key = (kind, jax.default_backend(),
           _paged_pallas_requested()) + dataclasses.astuple(cfg)
    fn = _PREFILL_JIT_CACHE.pop(key, None)
    if fn is None:
        frozen = dataclasses.replace(cfg)   # defensive copy: later
        # mutations of the caller's cfg must not leak into the trace
        fn = build(frozen)
    _PREFILL_JIT_CACHE[key] = fn            # re-insert = move to back
    while len(_PREFILL_JIT_CACHE) > _PREFILL_JIT_LIMIT:
        _PREFILL_JIT_CACHE.pop(next(iter(_PREFILL_JIT_CACHE)))
    return fn


def _serving_donate(*argnums):
    """Donation tuple for a serving entry point's device-resident state
    (KV cache, and the pipelined batcher's tok/pos/keys carry): saves
    one HBM copy per donated arg on accelerators; the CPU backend can't
    donate and would warn on every call."""
    return () if jax.default_backend() == "cpu" else argnums


def _jitted_prefill(cfg):
    return _serving_jit("prefill", cfg, lambda fz: jax.jit(
        lambda p, c, t: prefill(p, c, t, fz)))


def _jitted_prefill_chunk(cfg):
    # chunk width is a shape, so jax.jit re-specializes per width and
    # caches each; `start` stays dynamic (dynamic_slice inside)
    return _serving_jit("prefill_chunk", cfg, lambda fz: jax.jit(
        lambda p, c, t, s: prefill_chunk(p, c, t, s, fz)))


def _jitted_prefill_chunk_row(cfg):
    # admission variant: logits for ONE chunk row — skips the
    # O(width*vocab) head projection the caller would throw away
    return _serving_jit("prefill_chunk_row", cfg, lambda fz: jax.jit(
        lambda p, c, t, s, r: prefill_chunk(p, c, t, s, fz,
                                            logits_row=r)))


def _jitted_decode_step(cfg):
    return _serving_jit("decode_step", cfg, lambda fz: jax.jit(
        lambda p, c, t, pos: decode_step(p, c, t, pos, fz)))


def prefill_chunk(params, cache, tokens, start, cfg, logits_row=None,
                  attend_limit=None):
    """Process a CHUNK of C tokens beginning at dynamic position
    `start`, writing their K/V into the cache and returning the logits
    after every chunk position ([B, C, vocab]) — or, with
    `logits_row` (dynamic scalar), only that row's logits [B, vocab]:
    the admission path of continuous batching needs one row and skips
    the O(C*vocab) head projection.

    `attend_limit` (STATIC int) restricts the attention contraction to
    the first `attend_limit` cache positions — exact (the mask zeroes
    the tail anyway) whenever the caller knows start+C <= limit, e.g.
    the whole-prompt prefill at start=0, which otherwise pays a
    max_len-wide score matrix for a prompt-wide prompt.

    The chunked middle ground between prefill (whole prompt at 0) and
    decode_step (one token): long prompts stream through in fixed-size
    chunks, and speculative decoding verifies k draft tokens in one
    pass. Row i of the chunk attends cache positions <= start+i, so
    stale cache entries beyond the verified stream are never read (and
    are overwritten when re-processed).

    A Mamba layer has no such healing. Its state continues from the
    cache's (a zeroed cache at start 0, a cached prefix's state at a
    prefix hit, the previous chunk's otherwise) and, with `logits_row`,
    stops after that row: the rows behind it are the bucket's padding
    and leave no trace (ssm.mixer_seq's valid_len). Without
    `logits_row` every row of the chunk is real."""
    params = _maybe_dequantize(params)
    c = tokens.shape[1]
    try:
        concrete_end = int(start) + c      # eager path only; traced
    except Exception:                      # starts check inside jit is
        concrete_end = None                # the caller's contract
    if concrete_end is not None and concrete_end > cfg.max_len:
        raise ValueError(
            "chunk [%d, %d) overruns max_len %d (dynamic_update_slice "
            "would clamp and corrupt earlier cache positions)"
            % (concrete_end - c, concrete_end, cfg.max_len))
    x = params["embed"][tokens]
    if _learned_pos(cfg):
        x = x + jax.lax.dynamic_slice_in_dim(params["pos"], start, c, 0)
    positions = start + jnp.arange(c)      # chunk row i sits at start+i

    def contract(q, view):
        if attend_limit is not None:
            view = {name: arr[:, :attend_limit]
                    for name, arr in view.items()}
        return _cached_attention(q, view, positions, cfg, q.dtype)

    def latent(q, layer, rows, p):
        # the bucket's padding behind logits_row sees what that row sees
        # and no further: its blocks of queries stop there too
        return _latent_chunk_attention(
            q, {name: arr[:, :attend_limit] for name, arr in layer.items()},
            positions if logits_row is None
            else jnp.minimum(positions, start + logits_row), p, cfg)

    def window_contract(q, view, first):
        return _cached_attention(q, view, positions, cfg, q.dtype,
                                 _window(cfg), first)

    store, read = _dense_rows(cfg, start, contract)
    valid_len = None if logits_row is None else logits_row + 1
    mix = _mixer(cfg, _cache_attend(cfg, positions, store, read),
                 _latent_attend(cfg, positions, store, latent),
                 valid_len=valid_len, window=_cache_attend(
                     cfg, positions, *_ring_rows(cfg, start, window_contract,
                                                 valid_len), read_first=True))
    x, new_cache = _run_layers(x, params, cache, cfg, mix)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    if logits_row is not None:
        xr = jax.lax.dynamic_index_in_dim(x, logits_row, 1,
                                          keepdims=False)
        return jnp.einsum("bd,vd->bv", xr, _head(params, cfg)), new_cache
    return jnp.einsum("bcd,vd->bcv", x, _head(params, cfg)), new_cache


def _spec_core(params, draft_params, prompt, cfg, dcfg, k, n_new):
    """The whole speculative generation as ONE traceable program:
    prefill both models, then a lax.while_loop of rounds — draft scan
    (k small-model steps), one big-model verify chunk, device-side
    acceptance and a masked window write into the token buffer. The
    loop runs entirely on device; the host syncs once, on the result.

    Acceptance math: drafts agree with the big model's argmax `target`
    on a leading prefix; since drafts[i] == target[i] inside it, the
    round's emissions are simply target[:accepted+1] (the +1 being the
    corrected/bonus token), clamped to the remaining budget.

    `n_new` is TRACED (the loop bound is data): one compiled program
    serves every budget at a given prompt length — buffers size by
    cfg.max_len, the caller slices. Varying n_new costs nothing;
    only a new prompt length (or a k re-clamp near max_len)
    re-specializes, like any jit shape."""
    t_prompt = prompt.shape[1]
    total = t_prompt + n_new
    cache = init_cache(cfg, 1)
    dcache = init_cache(dcfg, 1)
    logits, cache = prefill(params, cache, prompt, cfg)
    _, dcache = prefill(draft_params, dcache, prompt, dcfg)
    # pad the buffer so the fixed-width (k+1) window write near the
    # budget edge stays in bounds; emissions beyond `total` are masked
    buf = jnp.zeros((cfg.max_len + k + 1,), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt[0], (0,))
    buf = buf.at[t_prompt].set(
        jnp.argmax(logits[0]).astype(jnp.int32))
    acc_log = jnp.zeros((cfg.max_len,), jnp.int32)  # >= 1 token/round

    def cond(state):
        return state[0] < total

    def body(state):
        n, buf, cache, dcache, acc_log, rounds = state
        tok0 = jax.lax.dynamic_slice(buf, (n - 1,), (1,))

        def dbody(carry, i):
            tok, dc = carry
            dlogits, dc = decode_step(draft_params, dc, tok,
                                      n - 1 + i, dcfg)
            nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            return (nxt, dc), nxt[0]

        (_, dcache), drafts = jax.lax.scan(
            dbody, (tok0, dcache), jnp.arange(k))
        # one big-model pass verifies all k proposals: the k+1 chunk
        # rows are the contexts ending at buf[n-1], d1, ..., d_k, so
        # row i predicts position n+i (row k is the bonus after a full
        # acceptance)
        window = jnp.concatenate([tok0, drafts])[None]
        vlogits, cache2 = prefill_chunk(params, cache, window,
                                        n - 1, cfg)
        target = jnp.argmax(vlogits[0], axis=-1).astype(jnp.int32)
        accepted = jnp.cumprod(
            (drafts == target[:k]).astype(jnp.int32)).sum()
        emit = jnp.minimum(accepted + 1, total - n)
        old = jax.lax.dynamic_slice(buf, (n,), (k + 1,))
        new = jnp.where(jnp.arange(k + 1) < emit, target, old)
        buf = jax.lax.dynamic_update_slice(buf, new, (n,))
        acc_log = acc_log.at[rounds].set(accepted)
        return (n + emit, buf, cache2, dcache, acc_log, rounds + 1)

    state = (jnp.int32(t_prompt + 1), buf, cache, dcache, acc_log,
             jnp.int32(0))
    n, buf, _, _, acc_log, rounds = jax.lax.while_loop(cond, body,
                                                       state)
    return buf[None], acc_log, rounds


def speculative_generate(params, draft_params, prompt, n_new, cfg,
                         draft_cfg, k_draft=4, return_stats=False):
    """Greedy speculative decoding: a small DRAFT model proposes
    k_draft tokens per round, the big model verifies them all in ONE
    prefill_chunk pass, and the longest agreeing prefix is accepted
    (plus the big model's corrected/bonus token). Every emitted token
    is the big model's greedy argmax — identical to generate() up to
    floating-point reduction-order ties between the chunked and
    per-token attention paths (argmax gaps below kernel noise, ~1e-6,
    can tip either way; any well-separated argmax matches exactly).
    Batch size 1 (acceptance length is data-dependent per row).
    Returns [1, Tp+n_new] int32 (with return_stats=True, also a dict
    of per-round acceptance counts and big-model launch count).

    The whole generation — both prefills and every draft/verify
    round — compiles to ONE device program (_spec_core), dispatched
    once: rounds advance in a lax.while_loop with the acceptance test
    on device, so tokens/s is bounded by model compute, not by
    host-loop syncs. The round count and per-round window
    width k are fixed at trace time; near the budget edge extra
    emissions are masked rather than re-shaped, and k is clamped so
    the fixed-width draft/verify writes stay inside both caches
    (cache writes beyond the verified stream self-heal: attention
    masks by position, and rejected-draft entries are overwritten by
    the next round before they become attendable).

    Both configs must share vocab_size."""
    if prompt.shape[0] != 1:
        raise ValueError("speculative decoding serves batch=1")
    for c in (cfg, draft_cfg):
        _refuse_dense_only(c, "speculative decoding (a rejected draft "
                          "cannot be rolled back)")
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError("draft and target must share the vocab")
    t_prompt = int(prompt.shape[1])
    total = t_prompt + n_new
    if total > min(cfg.max_len, draft_cfg.max_len):
        raise ValueError("prompt+n_new exceeds a model's max_len")
    if n_new < 1:
        raise ValueError("n_new must be >= 1")
    # deepest in-round write is position n-1+k with n <= total-1; keep
    # it inside BOTH caches (k_draft degrades gracefully near max_len)
    k = max(1, min(int(k_draft),
                   cfg.max_len - total + 1,
                   draft_cfg.max_len - total + 1))
    import dataclasses
    dfrozen = dataclasses.replace(draft_cfg)   # freeze like _serving_jit
    fn = _serving_jit(
        ("speculative", k, dataclasses.astuple(draft_cfg)), cfg,
        lambda fz: jax.jit(
            lambda p, dp, t, n: _spec_core(p, dp, t, fz, dfrozen,
                                           k, n)))
    out, acc_log, rounds = fn(params, draft_params, prompt,
                              jnp.int32(n_new))
    out = out[:, :total]          # host-side: n_new is data in-program
    if return_stats:
        rounds = int(rounds)
        return out, {"acceptances": [int(a) for a in
                                     np.asarray(acc_log)[:rounds]],
                     "big_model_launches": 1 + rounds}
    return out


def decode_step(params, cache, tokens, pos, cfg):
    """One autoregressive step.

    tokens [B] int32 (the token at position `pos`), pos scalar int32 —
    or int32 [B] for RAGGED decode (each row at its own position; what
    continuous batching needs, see models/serving.py). Returns
    (logits [B, vocab] for the NEXT token, updated cache).
    Static shapes throughout: `pos` is data, not shape, so one compiled
    program decodes every position. Accepts quantize_weights_int8
    trees: the dequantizing converts fuse into each weight's matmul.
    """
    return _decode(params, cache, None, tokens, pos, cfg)


def _decode(params, state, tables, tokens, pos, cfg, loads=None):
    """decode_step on either kind of K/V state: dense rows (`tables`
    None; pos a scalar or [B]) or a block pool behind `tables` (pos
    [B]). Both read through _decode_attention, the T_q = 1 row form;
    a latent layer's dense rows through _latent_decode_attention.
    `loads`: see _expert_ffn."""
    params = _maybe_dequantize(params)
    x = params["embed"][tokens]
    if _learned_pos(cfg):
        if jnp.ndim(pos) == 1:     # trace-time branch: [B] vs scalar
            x = x + jnp.take(params["pos"], pos, axis=0)
        else:
            x = x + jax.lax.dynamic_index_in_dim(
                params["pos"], pos, 0, keepdims=False)
    store, read = _kv_state(
        cfg, tables, pos,
        lambda q, view: _decode_attention(q, view, pos, cfg))
    mix = _mixer(cfg, _cache_attend(cfg, pos, store, read), _latent_attend(
        cfg, pos, store, lambda q, layer, rows, p: _latent_decode_attention(
            q, layer, pos, p, cfg)),
        window=_cache_attend(cfg, pos, *_ring_rows(
            cfg, pos, lambda q, view: _decode_attention(
                q, view, pos, cfg, _window(cfg)))))
    x, new_state = _run_layers(x, params, state, cfg, mix, loads)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return jnp.einsum("bd,vd->bv", x, _head(params, cfg)), new_state


# ------------------------------------------------------- paged decode ---
# The KV cache virtualized into fixed-size BLOCKS: one per-layer pool
# `[num_blocks, block_size, KVH, Dh]` shared by every lane, plus per-lane
# int32 block TABLES `[B, max_len // block_size]` mapping position range
# [j*bs, (j+1)*bs) to a pool block. Capacity decouples from max_len — a
# lane holds exactly the blocks its context needs, and a block mapped
# into two tables (shared prefix) is stored once. Block 0 is the
# reserved NULL block: unallocated table entries point at it, so decode
# writes past a lane's allocation land in a shared garbage sink (never
# attendable for a live request — attention masks to <= pos, and the
# allocator covers every live position with a real block) instead of
# corrupting a neighbour. Reads gather the pool through the table into
# the dense [B, T] layout and reuse the SAME attention contractions as
# the dense cache (_decode_attention and its int8/GQA/flash variants):
# the gathered view carries bit-identical values at every unmasked
# position, which is what keeps paged == dense == solo generate()
# bit-exact rather than approximately equal. Allocation policy (free
# list, refcounts, copy-on-extend sharing) lives in models/serving.py —
# this layer is purely the compiled read/write geometry.

def init_paged_cache(cfg, num_blocks, block_size):
    """Zeroed per-layer block pools. Layout matches init_cache with the
    position axis split into [num_blocks, block_size]; under
    kv_cache_int8 the per-(position, head) fp32 scale planes split the
    same way ([num_blocks, block_size, KVH]), so a block carries its
    own scales and int8-KV composes per block."""
    _refuse_dense_only(cfg, "the paged KV pool (init_paged_cache)")
    if num_blocks < 2:
        raise ValueError("need >= 2 blocks (block 0 is the null block)")
    return [_kv_leaves(cfg, num_blocks, block_size)
            for _ in range(cfg.n_layers)]


def paged_cache_nbytes(cfg, num_blocks, block_size):
    """Analytic byte size of the pool :func:`init_paged_cache` would
    build — mirrors its dtype geometry (int8 k/v + fp32 scale planes
    under kv_cache_int8, else ``cfg.dtype``) without allocating. The
    memory budget's preflight for pool init/grow reads this."""
    hd = _head_dim(cfg)
    cells = num_blocks * block_size * _kvh(cfg)
    if cfg.kv_cache_int8:
        per_layer = 2 * cells * hd * 1 + 2 * cells * 4   # k/v + ks/vs
    else:
        per_layer = 2 * cells * hd * jnp.dtype(cfg.dtype).itemsize
    return int(per_layer * cfg.n_layers)


def grow_paged_cache(pool, extra_blocks):
    """The pool with ``extra_blocks`` fresh zero blocks appended to
    every leaf's block axis. Existing blocks keep their ids and values
    (a pure concat — no copy of live data semantics change), so block
    tables remain valid and the allocator simply extends its free list
    with the new ids."""
    if extra_blocks <= 0:
        return pool
    def g(leaf):
        pad = jnp.zeros((extra_blocks,) + leaf.shape[1:], leaf.dtype)
        return jnp.concatenate([leaf, pad], axis=0)
    return [{name: g(leaf) for name, leaf in layer.items()}
            for layer in pool]


def _paged_gather(layer_pool, tables):
    """Gather one layer's pool through the block tables into the dense
    [B, NB*bs, ...] cache layout — ONE fused XLA gather feeding the
    same attention contraction as the dense path (no Pallas). Table
    entry j covers positions [j*bs, (j+1)*bs), so the flattened axis is
    in position order and the `<= pos` mask applies unchanged."""
    b, nb = tables.shape
    flat = tables.reshape(-1)

    def g(leaf):
        got = jnp.take(leaf, flat, axis=0)        # [B*NB, bs, ...]
        return got.reshape((b, nb * leaf.shape[1]) + leaf.shape[2:])

    return {name: g(leaf) for name, leaf in layer_pool.items()}


def _paged_blocks(cfg, tables, where, contract):
    """Paged blocks: the dense rows' leaves as pools
    [num_blocks, block_size, KVH, ...], reached through `tables`
    [B, max_len // bs]. `where` is [B] (row i's one k/v at where[i]) or
    [B, C] (row i's window at where[i, :]); a position goes to block
    tables[i, position // bs] at offset position % bs."""
    def store(layer, **fresh):
        bs = layer["k"].shape[1]
        if where.ndim == 1:
            # a position past the table (a retired lane coasting to its
            # chunk boundary) clamps to the last entry, which the
            # allocator guarantees is never a shared block; an
            # unallocated entry is the null block. Either way the
            # garbage is unreadable
            blk = jnp.take_along_axis(tables, (where // bs)[:, None],
                                      axis=1)[:, 0]
        else:
            # positions past the TABLE (beyond max_len) are routed to
            # the null block: clamping to the last entry is not safe
            # for a window, because a near-budget lane's window can
            # overrun while the lane is still live and its last block
            # still attendable. Unallocated entries are the null block
            # as usual
            nb = tables.shape[1]
            blk_idx = where // bs                                # [B, C]
            blk = jnp.take_along_axis(
                tables, jnp.clip(blk_idx, 0, nb - 1), axis=1)
            blk = jnp.where(blk_idx < nb, blk, 0)
        off = where % bs

        def put(leaf, arr):
            return leaf.at[blk, off].set(arr)

        return _kv_store(layer, fresh, cfg, put)

    def read(q, layer, k, v):
        if not _paged_pallas_requested():
            # the gathered view carries bit-identical values at every
            # unmasked position: paged == dense == solo stays exact
            return contract(q, _paged_gather(layer, tables))
        # batched-lane megakernel: reads the pool THROUGH the tables
        # (no dense gather copy), skips dead blocks per lane; a ragged
        # [B, k+1] spec-verify window is just the k>1 case of the
        # decode grid. The batcher's membudget preflight already covers
        # this jit boundary (it preflights every dispatch fn), and the
        # scope makes its bytes attributable via hlo/attribution.
        from ..kernels import paged_attention
        from ..observability import attribution as _obs_attr
        row = q.ndim == 3          # decode's one row is a span of 1
        scope = "paged_decode_kernel" if row else "paged_verify_kernel"
        _obs_attr.note_scope(scope)
        with jax.named_scope(scope):
            o = paged_attention(q[:, None] if row else q, layer, tables,
                                where if row else where[:, 0])
        return o[:, 0] if row else o

    return store, read


def _kv_state(cfg, tables, where, contract):
    """The (store, read) pair of the K/V state the caller holds: dense
    rows, or with `tables` the block pool behind them."""
    if tables is None:
        return _dense_rows(cfg, where, contract)
    return _paged_blocks(cfg, tables, where, contract)


def decode_step_paged(params, pool, tables, tokens, pos, cfg):
    """One ragged autoregressive step through the block tables.

    tokens [B] int32, pos [B] int32 (always ragged — this is the
    continuous-batching entry point), tables [B, max_len//bs] int32.
    Returns (logits [B, vocab], updated pool); the tables themselves
    are read-only here — allocation is the host scheduler's job.
    Everything the dense step supports composes: RoPE (keys cached
    rotated), GQA (the gathered view keeps KVH heads; the grouped
    contraction reads each once per group), int8-KV (codes + per-block
    scales gathered together, the one shared _int8_cache_attention
    does the rest), quantized weight trees."""
    _refuse_dense_only(cfg, "paged decode (decode_step_paged)")
    return _decode(params, pool, tables, tokens, pos, cfg)


def _decode_step_on(params, state, tables, tokens, pos, cfg, loads=None):
    """decode_step on whichever K/V state a scheduler holds, through
    that kind's own door: `tables` None is the dense cache. `loads`:
    see _expert_ffn."""
    if tables is not None:
        _refuse_dense_only(cfg, "paged decode (decode_step_paged)")
    return _decode(params, state, tables, tokens, pos, cfg, loads)


# ------------------------------------------------------ batched verify ---
# The ragged-chunk forward that batched speculative decoding needs: C
# tokens per lane, each lane's window anchored at its OWN position. Both
# variants share the attention contraction with prefill_chunk
# (_cached_attention), which is what keeps batched verify bit-exact
# with the stepped decode it replaces.

def verify_chunk(params, cache, tokens, pos, cfg):
    """Process a RAGGED chunk: C tokens PER LANE, lane b's window
    starting at its own position pos[b] ([B] int32 — data, not shape,
    like every serving entry point). Row (b, i) carries the stream
    token at position pos[b]+i, writes its K/V there, attends cache
    positions <= pos[b]+i, and its logits predict position pos[b]+i+1.
    This is the batched generalization of prefill_chunk (whose `start`
    is one scalar for the whole batch) and the target pass of batched
    speculative decoding: the [B, k+1] window [tok, d_1..d_k] yields
    every lane's verification targets in ONE dispatch.

    Stale K/V from rejected drafts heals by position exactly as the
    solo _spec_core documents: the next round's window starts at the
    first rejected position and rewrites every stale position before
    any row can attend it. Windows that run past max_len (a parked
    lane, a near-budget lane coasting) DROP their writes instead of
    clamping. Returns (logits [B, C, vocab], cache)."""
    _refuse_dense_only(cfg, "speculative verification (verify_chunk: a "
                      "rejected draft cannot be rolled back)")
    return _verify(params, cache, None, tokens, pos, cfg)


def _verify(params, state, tables, tokens, pos, cfg):
    """verify_chunk on either kind of K/V state: dense rows (`tables`
    None) or a block pool behind `tables`."""
    params = _maybe_dequantize(params)
    c = tokens.shape[1]
    positions = pos[:, None] + jnp.arange(c)[None, :]        # [B, C]
    x = params["embed"][tokens]
    if _learned_pos(cfg):
        # take() clamps OOB rows — their logits are garbage, but their
        # writes drop and their emissions are never credited
        x = x + jnp.take(params["pos"], positions, axis=0)
    mix = _mixer(cfg, _cache_attend(cfg, positions, *_kv_state(
        cfg, tables, positions,
        lambda q, view: _cached_attention(q, view, positions, cfg,
                                          q.dtype))))
    x, new_state = _run_layers(x, params, state, cfg, mix)
    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return jnp.einsum("bcd,vd->bcv", x, _head(params, cfg)), new_state


def verify_chunk_paged(params, pool, tables, tokens, pos, cfg):
    """verify_chunk through the block tables: same ragged-window
    semantics, writes scattered into the pool (_paged_blocks), reads
    through the gathered dense view (_paged_gather) into the SAME
    attention contraction as the dense verify — bit-identical values
    at every unmasked position, so paged == dense == solo stays exact
    under speculation. Tables are read-only here; allocation
    (including the speculative over-reserve and release-on-reject) is
    the host scheduler's job.
    Returns (logits [B, C, vocab], pool)."""
    _refuse_dense_only(cfg, "paged speculative verification "
                      "(verify_chunk_paged)")
    return _verify(params, pool, tables, tokens, pos, cfg)


def _verify_chunk_on(params, state, tables, tokens, pos, cfg):
    """verify_chunk on whichever K/V state a scheduler holds, through
    that kind's own door: `tables` None is the dense cache."""
    if tables is None:
        return verify_chunk(params, state, tokens, pos, cfg)
    return verify_chunk_paged(params, state, tables, tokens, pos, cfg)


def make_decode_step(cfg):
    """Jitted decode_step with the cache donated (updated in place)."""
    def step(params, cache, tokens, pos):
        return decode_step(params, cache, tokens, pos, cfg)
    return jax.jit(step, donate_argnums=(1,))


def _sample_logits(logits, key, temperature, top_k, top_p):
    """One sampling step over [B, V] logits — temperature scaling,
    static top-k truncation, and nucleus (top-p) filtering, all
    jit-compatible (static shapes; masking instead of gathering)."""
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k is not None and 0 < top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep every token whose PRECEDING cumulative mass < top_p (the
        # first token is always kept)
        keep_sorted = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < top_p],
            axis=-1)
        # threshold logit = smallest kept logit per row
        thresh = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _generate_core(params, prompt, cache, key, n_new, cfg, greedy,
                   temperature, top_k, top_p):
    """prefill + decode scan, one traceable program (see generate)."""
    b, t_prompt = prompt.shape
    total = t_prompt + n_new
    buf = jnp.zeros((b, total), jnp.int32).at[:, :t_prompt].set(prompt)

    def choose(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return _sample_logits(logits, sub, temperature, top_k,
                              top_p), key

    last_logits, cache = prefill(params, cache, prompt, cfg)
    nxt, key = choose(last_logits, key)
    buf = buf.at[:, t_prompt].set(nxt)

    def body(carry, pos):
        buf, cache, key = carry
        tok = jax.lax.dynamic_index_in_dim(buf, pos, 1, keepdims=False)
        logits, cache = decode_step(params, cache, tok, pos, cfg)
        nxt, key = choose(logits, key)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], pos + 1, axis=1)
        return (buf, cache, key), None

    if n_new > 1:
        (buf, _, _), _ = jax.lax.scan(
            body, (buf, cache, key),
            jnp.arange(t_prompt, total - 1))
    return buf


def generate(params, prompt, n_new, cfg, greedy=None, seed=0,
             temperature=1.0, top_k=None, top_p=None, mesh=None):
    """Autoregressive generation: prompt [B, Tp] int32 -> [B, Tp+n_new].

    Sampling: by default, passing any of `temperature` (!= 1.0),
    `top_k`, or `top_p` samples with those controls; otherwise decoding
    is greedy argmax. Passing greedy=True together with sampling
    controls is a contradiction and raises. With `mesh`, the KV cache
    is laid out dp/tp-sharded (shard_cache) to match TP-sharded params.
    The prompt is prefilled in ONE batched forward (prefill), then the
    generation steps run as one lax.scan.

    Both the mesh-sharded and single-device calls run as ONE cached
    jitted program (keyed on cfg + the sampling controls;
    n_new/prompt-length/input-sharding re-specialize like any shape) —
    repeated generate() calls pay zero re-trace, which is what a
    serving loop needs (benchmark/serving_bench.py measures this).
    """
    sampling_requested = (temperature != 1.0 or top_k is not None
                          or top_p is not None)
    if greedy is None:
        greedy = not sampling_requested
    elif greedy and sampling_requested:
        raise ValueError(
            "greedy=True ignores temperature/top_k/top_p — pass "
            "greedy=False (or omit greedy) to sample")
    b, t_prompt = prompt.shape
    total = t_prompt + n_new
    if total > cfg.max_len:
        raise ValueError("prompt+n_new %d exceeds max_len %d"
                         % (total, cfg.max_len))
    if n_new == 0:
        return prompt
    cache = init_cache(cfg, b)
    if mesh is not None:
        # jit specializes per input sharding, so the sharded and
        # single-device calls share one cached wrapper
        cache = shard_cache(cache, cfg, mesh)
    key = jax.random.PRNGKey(seed)
    fn = _serving_jit(
        ("generate", bool(greedy), float(temperature), top_k, top_p),
        cfg,
        lambda fz: jax.jit(
            lambda p, t, c, k, n: _generate_core(
                p, t, c, k, n, fz, greedy, temperature, top_k, top_p),
            static_argnums=(4,), donate_argnums=_serving_donate(2)))
    return fn(params, prompt, cache, key, n_new)


def beam_search(params, prompt, n_new, cfg, beam=4, length_penalty=0.0,
                mesh=None):
    """Beam-search decoding over the KV cache: prompt [B, Tp] ->
    (sequences [B, beam, Tp+n_new], scores [B, beam]), beams sorted
    best-first by total log-probability (optionally length-normalized
    by (Tp+n_new)^length_penalty).

    The cache rides at batch width B*beam; each step re-gathers the
    cache rows of the surviving beams' parents (a batched take inside
    the scan — static shapes, one compiled program for the loop).
    beam=1 reduces exactly to greedy generate(). Quantized trees pass
    through (dequant fuses inside the compiled steps); with `mesh`,
    the expanded cache is laid out dp/tp-sharded like generate()'s."""
    b, t_prompt = prompt.shape
    total = t_prompt + n_new
    if total > cfg.max_len:
        raise ValueError("prompt+n_new %d exceeds max_len %d"
                         % (total, cfg.max_len))
    if n_new < 1:
        raise ValueError("beam search needs n_new >= 1")
    if not 1 <= beam <= cfg.vocab_size:
        raise ValueError("beam width %d must be in [1, vocab_size=%d]"
                         % (beam, cfg.vocab_size))
    k = beam

    cache = init_cache(cfg, b)
    if mesh is not None:
        cache = shard_cache(cache, cfg, mesh)
    # one cached jitted program per (cfg, beam, penalty, mesh) — like
    # generate(), repeated beam_search() calls pay zero re-trace
    fn = _serving_jit(
        ("beam", k, float(length_penalty), mesh), cfg,
        lambda fz: jax.jit(
            lambda p, t, c, n: _beam_core(p, t, c, n, k,
                                          length_penalty, fz, mesh),
            static_argnums=(3,), donate_argnums=_serving_donate(2)))
    return fn(params, prompt, cache, n_new)


def _beam_core(params, prompt, cache, n_new, k, length_penalty, cfg,
               mesh):
    """prefill + beam expansion + decode scan, one traceable program
    (see beam_search)."""
    b, t_prompt = prompt.shape
    total = t_prompt + n_new
    vocab = cfg.vocab_size
    last_logits, cache = prefill(params, cache, prompt, cfg)
    logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)

    # first expansion: top-k tokens of the last prompt position seed
    # the beams; the cache is replicated per beam (rows grouped as
    # [b0*k beams..., b1*k beams, ...])
    scores, tok0 = jax.lax.top_k(logp0, k)            # [B, k]
    rep = lambda x: jnp.repeat(x, k, axis=0)
    cache = jax.tree.map(rep, cache)
    if mesh is not None:
        # traced equivalent of shard_cache for the beam-expanded rows
        cache = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, _cache_pspec(cfg, x))), cache)
    buf = jnp.zeros((b * k, total), jnp.int32)
    buf = buf.at[:, :t_prompt].set(jnp.repeat(prompt, k, axis=0))
    buf = buf.at[:, t_prompt].set(tok0.reshape(-1))

    def body(carry, pos):
        buf, cache, scores = carry
        tok = jax.lax.dynamic_index_in_dim(buf, pos, 1, keepdims=False)
        logits, cache = decode_step(params, cache, tok, pos, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        cand = scores.reshape(b, k, 1) + logp.reshape(b, k, vocab)
        scores, flat = jax.lax.top_k(cand.reshape(b, k * vocab), k)
        parent = flat // vocab                         # [B, k]
        token = (flat % vocab).astype(jnp.int32)
        # re-gather the surviving parents' rows
        row = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
        cache = jax.tree.map(lambda x: jnp.take(x, row, axis=0), cache)
        buf = jnp.take(buf, row, axis=0)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, token.reshape(-1, 1), pos + 1, axis=1)
        return (buf, cache, scores), None

    if n_new > 1:
        (buf, _, scores), _ = jax.lax.scan(
            body, (buf, cache, scores),
            jnp.arange(t_prompt, total - 1))
    if length_penalty:
        scores = scores / (float(total) ** length_penalty)
    # beams emerge sorted (top_k order is descending)
    return buf.reshape(b, k, total), scores


def make_train_step(cfg, mesh=None, lr=1e-2, guard=False):
    """Jitted full training step: (params, opt_state, tokens) ->
    (params, opt_state, loss). SGD with momentum, all-reduce of grads is
    implicit in GSPMD (grads inherit param shardings).

    With ``guard=True`` the step returns a fourth output ``skipped``
    (device bool) and applies the NON-FINITE STEP GUARD entirely on
    device: if the loss or any gradient is NaN/Inf, params and momentum
    pass through untouched — one divergent batch can never poison the
    weights, and an uninterrupted guarded run stays bit-identical to
    the unguarded one as long as nothing trips (the selects choose the
    same updated arrays)."""

    def step(params, momentum, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        new_m = jax.tree.map(lambda m, g: 0.9 * m + g, momentum, grads)
        new_p = jax.tree.map(lambda p, m: p - lr * m.astype(p.dtype),
                             params, new_m)
        if not guard:
            return new_p, new_m, loss
        ok = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
        params = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                              new_p, params)
        momentum = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                new_m, momentum)
        return params, momentum, loss, jnp.logical_not(ok)

    return jax.jit(step, donate_argnums=(0, 1))


def init_momentum(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)


# sharded checkpoint/resume for this stack lives in models/checkpoint.py;
# re-exported here so the flagship's whole train/serve/persist surface is
# reachable from one module
from .checkpoint import (save_checkpoint, load_checkpoint,  # noqa: E402
                         restore_train_state, resume_from_latest,
                         CheckpointCorrupt, wait_for_pending_save,
                         install_emergency_checkpoint,
                         uninstall_emergency_checkpoint)
