"""Continuous batching for autoregressive serving.

A fixed pool of B cache slots decodes as ONE ragged batch (each row at
its own position — `decode_step` with vector `pos`); requests are
admitted into free slots mid-stream and leave when done, so the batch
never drains to refill (the reference serves Module.predict batch-at-
a-time: `/root/reference/python/mxnet/module/base_module.py:336-420`;
continuous batching is the TPU-serving upgrade of that surface —
static shapes, one compiled step program, no pipeline bubbles between
requests).

Design notes (all static-shape, XLA-friendly):

* One compiled ragged decode step serves every mix of positions — pos
  is data, not shape.
* Admission prefills the prompt at a power-of-two BUCKET width (one
  compiled prefill per bucket, not per prompt length) with the logits
  row for the true last token selected out. Pad garbage in the K/V
  rows beyond the prompt is harmless: attention masks to `<= pos`, and
  positions beyond the prompt are overwritten by decode writes before
  they ever become attendable — the same self-healing argument the
  speculative decoder relies on.
* HYBRID models (cfg.layer_kinds with "mamba", "mamba2" or "kda"
  layers) give a lane two kinds of state: rows for the attention layers
  (K/V heads, or an "mla" layer's one latent a position) and a
  fixed-size recurrent state for the others (conv window + a float32 SSM
  state, a channel's in Mamba-1 and a head's in Mamba-2, or a KDA
  layer's float32 matrix a head). An "ffn" block (a model whose blocks
  hold one sub-layer each) keeps nothing: its state has no leaves.
  That state has no self-healing: a padded position folded into it
  stays. It is exact by construction instead — the bucketed prefill
  stops it at the last real token (prefill_chunk's logits_row), a
  cached prefix carries the state at its end, a continuation prefills
  its history again. Mechanisms that cannot carry it or latent rows
  (paged blocks, speculative rollback, int8 KV) are refused at
  construction.
* ROUTED EXPERTS (cfg.n_experts): the decode program returns beside
  its tokens the dispatch's routing counts
  (tf.MOE_STATS), which step() adds to the counters moe.<name> while
  spans record (the speculative programs count nothing).
* HYPER-CONNECTIONS (cfg.hc_mult): the residual stream is n streams a
  token across DEPTH, inside a program; nothing a lane keeps changes
  (the streams are summed before the final norm). An admission's chunks
  are sized by the n-stream carry (prefill_widths), and while spans
  record the counter hc.rows counts the token rows x sub-layers every
  dispatch and admission passed through the frame.
* Idle slots keep lanes busy writing at position 0 of retired rows;
  the next admission's prefill overwrites them. Throughput is
  proportional to active lanes, latency to the slowest active row —
  exactly the continuous-batching trade.
* ONE decode loop, step(), over a window of `pipeline_depth` chunk
  dispatches in flight (the default is 2): the decode carry — cache,
  per-lane tokens/positions, sample keys — stays device-resident, so
  chunk k+1 dispatches against chunk k's output buffers before anyone
  syncs chunk k's emissions: the host's share of a round (fetch, retire
  loop, bookkeeping) runs while the device works on the next one.
  Admission/eviction are jitted lane patches sequenced after the
  in-flight chunks; emissions are credited by dispatch-time lane
  identity, which is what keeps every stream bit-identical at every
  width and to solo generate(). A window of one (pipeline_depth=1)
  dispatches a chunk and syncs it in the same step().

* PAGED KV cache (paged=True / MXNET_KV_PAGED): the per-lane dense
  [max_len] cache rows become one per-layer block pool + per-lane int32
  block tables (tf.init_paged_cache / tf.decode_step_paged — reads are
  a fused gather into the same dense contraction, so streams stay
  bit-exact). Admission accounts in BLOCKS against a refcounting
  free-list allocator: capacity = pool blocks, not lanes x max_len,
  blocks allocate lazily as positions advance (against an
  admission-time reservation) and free on finish/evict, and
  cache_prefix becomes refcounted block SHARING (full prefix blocks
  stored once, copy-on-extend for partial tails, freed at refcount
  zero). Composes with int8-KV (quantized pool + per-block scales),
  GQA, chunking, pipelining (the carry holds pool + tables), and the
  dispatch-failure requeue path.

* SPECULATIVE dispatches (spec_k / MXNET_SPEC_K): every decode round
  drafts k tokens per lane — from a small draft model or, by default,
  n-gram prompt-lookup against the lane's own stream — then verifies
  all lanes' [k+1] windows in ONE ragged target pass
  (tf.verify_chunk / verify_chunk_paged) with device-side cumprod
  acceptance, so the accepted prefix + one free token land per lane
  per dispatch (1..k+1 tokens instead of exactly 1). Rejected cache
  writes heal by position (`attention <= pos`, as everywhere above);
  paged block tables advance by ACCEPTED counts with worst-case draft
  blocks released at sync; pipelining keeps depth speculative
  dispatches in flight; a per-lane adaptive-k controller
  (MXNET_SPEC_ACCEPT_FLOOR) shrinks the draft width where measured
  acceptance is poor. Greedy-only, and bit-exact vs solo generate()
  — the accept test IS the target argmax.

Greedy decoding (the serving default); sampling per-row is a
straightforward extension (thread a per-slot PRNG key through step()).
Weight-only int8 trees (quantize_weights_int8) pass through unchanged.
"""

import dataclasses
import json
import os
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from . import journal as _journal
from . import transformer as tf
from .. import _fastenv
from ..observability import attribution as _attr
from ..observability import chaos as _chaos
from ..observability import core as _obs
from ..observability import events as _events
from ..observability import flight as _flight
from ..observability import http as _obs_http
from ..observability import recompile as _obs_recompile
from ..observability import timeseries as _timeseries
from ..observability import integrity as _integrity
from ..observability import membudget as _membudget
from ..observability import slo as _slo

DEFAULT_KV_BLOCK_SIZE = 16


def _bucket(n, lo=8):
    b = lo
    while b < n:
        b *= 2
    return b


# the stream elements (tokens x streams x d_model; `hc_mult` streams a
# token, else one) ONE prefill call of an admission may hold: a longer
# run of tokens goes through prefill_chunk in whole chunks of the
# largest power of two under it and a bucketed rest, so that an
# admission's temporaries (some dozens of copies of the chunk's stream,
# and k of them in a routed layer) are bounded by the model's width and
# not by the prompt. 2^25: 16,384 tokens of a 2,048-wide stream, 4,096
# of a 7,168-wide one, 2,048 of four streams of 3,584
PREFILL_CHUNK_ELEMS = 1 << 25


def prefill_widths(cfg, n, start=0, pad=True):
    """The widths of the prefill calls that take `n` tokens into a lane's
    row from position `start`: whole chunks, then the rest at its
    bucket's width (`pad` False: at its exact length), clamped to the
    row's end: the bucket can pass max_len (max_len 96, a rest of 70 ->
    128) and the row is max_len wide; the caller has checked that
    start + n <= max_len, so a width never falls under its tokens."""
    chunk = 8
    while 2 * chunk * (cfg.hc_mult or 1) * cfg.d_model \
            <= PREFILL_CHUNK_ELEMS:
        chunk *= 2
    whole, rest = divmod(n, chunk)
    widths = [chunk] * whole
    if rest:
        widths.append(min(_bucket(rest) if pad else rest,
                          cfg.max_len - start - whole * chunk))
    return widths


def _pick_next(logits, keys, greedy, temperature, top_k, top_p):
    """Every lane's next token from its logits row: argmax, or a sample.

    Sampling mirrors generate()'s key chain PER ROW (split the row's
    key, sample with the sub-key), so a request's sampled stream is
    identical to its solo generate(seed=...) run — slot placement and
    pool mix cannot perturb it. Returns (tokens [B], the advanced
    keys)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
    split = jax.vmap(jax.random.split)(keys)   # [B, 2, 2]
    keys, subs = split[:, 0], split[:, 1]
    nxt = jax.vmap(
        lambda l, k: tf._sample_logits(
            l[None], k, temperature, top_k, top_p)[0]
    )(logits, subs)
    return nxt, keys


# The decode programs take (params, cache, tables, tok, pos, keys) for
# either kind of cache: `tables` is None for the dense rows (an empty
# pytree: no argument reaches the device) and the per-lane block tables
# for the paged pool, and tf._decode_step_on picks the step at trace
# time. The cache / pool and the tables are donated (the chunk carries
# both device-resident). `paged` is part of each _serving_jit key, so
# the two kinds never share a wrapper.

def _decode_and_pick(fz, controls, params, cache, tables, tok, pos, keys):
    """One ragged decode step and every lane's next token. Returns
    (tokens, keys, cache, routing): the round's routing counts
    (tf.moe_stats) for a model with routed experts, else None."""
    loads = [] if fz.n_experts else None
    logits, cache = tf._decode_step_on(params, cache, tables, tok, pos,
                                       fz, loads)
    nxt, keys = _pick_next(logits, keys, *controls)
    return nxt, keys, cache, \
        tf.moe_stats(loads, tok.shape[0], fz) if loads else None


def _scan_steps(fz, controls, k, params, cache, tables, tok, pos, keys):
    """`k` ragged decode steps as one lax.scan; returns the rolling
    carry (cache, last token, advanced positions, key chain) and the
    [k, B] emissions with each step's routing counts ([k, ...], or
    None: _decode_and_pick)."""
    def body(carry, _):
        cache, tok, pos, keys = carry
        nxt, keys, cache, routing = _decode_and_pick(
            fz, controls, params, cache, tables, tok, pos, keys)
        return (cache, nxt, pos + 1, keys), (nxt, routing)
    return jax.lax.scan(body, (cache, tok, pos, keys), None, length=k)


def _jitted_pipeline_chunk(cfg, greedy, temperature, top_k, top_p, k,
                           paged):
    """`k` ragged decode steps as ONE compiled program (lax.scan) that
    returns the WHOLE rolling carry (cache, tables, last token,
    advanced positions, key chain) alongside the [k, B] emissions — the
    batcher's one decode program, the unit step() dispatches.

    The carry never leaves the device, so chunk k+1 can be dispatched
    against chunk k's output buffers BEFORE anyone syncs chunk k's
    tokens. The emissions are the only output the host ever fetches;
    it applies the [k, B] block afterwards, discarding any tail a
    request emitted past its stop token or budget (bounded waste, the
    standard trade for multi-step scheduling: k steps a dispatch
    amortize the dispatch and the sync k-fold). The carry is
    donated on accelerators (tok/pos/keys included — they are dead the
    moment the next chunk is built from them); tables pass through
    unchanged (allocation patches apply between dispatches,
    host-side). A model with routed experts returns the chunk's summed
    routing counts as one more output."""
    controls = (greedy, temperature, top_k, top_p)

    def build(fz):
        def chunk(params, cache, tables, tok, pos, keys):
            (cache, tok, pos, keys), (toks, routing) = _scan_steps(
                fz, controls, k, params, cache, tables, tok, pos, keys)
            out = toks, cache, tables, tok, pos, keys    # toks [k, B]
            if routing is None:
                return out
            return out + (jnp.sum(routing, axis=0),)
        return jax.jit(chunk,
                       donate_argnums=tf._serving_donate(1, 2, 3, 4, 5))
    return tf._serving_jit(
        ("decode_pipeline", paged, greedy, float(temperature), top_k,
         top_p, k), cfg, build)


def _jitted_lane_patch(cfg):
    """Patch ONE lane of the device-resident (tok, pos, keys) carry —
    the admission / lane-clear primitive of the pipelined batcher.
    Runs as a tiny device program sequenced after whatever chunks are
    in flight (it consumes the last dispatch's output buffers), so a
    freed or freshly-admitted lane takes effect exactly at the next
    dispatch boundary, with no host round trip."""
    return tf._serving_jit("lane_patch", cfg, lambda fz: jax.jit(
        lambda tok, pos, keys, i, t, p, key: (
            tok.at[i].set(t), pos.at[i].set(p), keys.at[i].set(key)),
        donate_argnums=tf._serving_donate(0, 1, 2)))


def _jitted_admit_token(cfg, greedy, temperature, top_k, top_p):
    """First generated token from the prefill logits, chosen ON
    DEVICE: argmax under greedy, else generate()'s exact key chain
    (key = PRNGKey(seed); split once; sample with the sub-key; carry
    the key). The pipelined admit() pulls only this SCALAR to the
    host — not the [vocab] logits row — and the returned key patches
    straight into the key-chain carry."""
    def build(fz):
        def pick(last, seed):
            if greedy:
                return (jnp.argmax(last).astype(jnp.int32),
                        jnp.zeros((2,), jnp.uint32))
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            first = tf._sample_logits(last[None], sub, temperature,
                                      top_k, top_p)[0]
            return first, jnp.asarray(key, jnp.uint32)
        return jax.jit(pick)
    return tf._serving_jit(
        ("admit_token", greedy, float(temperature), top_k, top_p),
        cfg, build)


def _jitted_fresh_row(cfg):
    """The zeroed one-lane row an admission without a cached prefix
    starts from, tf.init_cache(cfg, 1), as ONE small program: no
    argument, nothing donated, the same tree, shapes and dtypes. Eagerly
    the row costs a launch a leaf (~100 one-element programs for a
    24-layer model), which the host issues while the device has only the
    round in flight to work on. Its zeros are broadcasts, not literals:
    the executable stays under a megabyte whatever the row's bytes."""
    def build(fz):
        def fresh_row():
            return tf.init_cache(fz, 1)
        return jax.jit(fresh_row)
    return tf._serving_jit("fresh_row", cfg, build)


def _jitted_slot_write(cfg):
    """Write a 1-row prefilled cache into slot `i` of the pool cache.

    The copy is deliberately FULL-ROW ([1, max_len] per layer, not the
    prompt's bucket width): it clears the previous occupant's K/V
    beyond the bucket, which is load-bearing for slot reuse — any
    future narrowing to bucket width must add an explicit tail-clear
    or retired requests' cache lines become attendable again once the
    new request decodes past its own prompt. A hybrid model's
    recurrent state leaves ride the same tree.map (batch first): the
    row's state replaces the previous occupant's whole."""
    return tf._serving_jit("slot_write", cfg, lambda fz: jax.jit(
        lambda full, row, i: jax.tree.map(
            lambda f, r: jax.lax.dynamic_update_slice_in_dim(
                f, r.astype(f.dtype), i, axis=0), full, row),
        donate_argnums=tf._serving_donate(0)))


# ---- paged-cache compiled programs -------------------------------------
# What moves blocks between a row cache and the per-layer pool, and the
# block-table patches. The decode programs above serve the pool too.

def _jitted_block_write(cfg, n):
    """Scatter `n` consecutive blocks of a [1, max_len] row cache
    (positions [start, start + n*bs)) into pool blocks `ids` — the
    paged admission's slot-write: only the NON-SHARED tail of a prompt
    is ever written, whole blocks at a time (so a freed-and-reallocated
    block is completely overwritten, no tail-clear needed)."""
    def build(fz):
        def wr(pool, row, ids, start):
            def leaf(pleaf, rleaf):
                bs = pleaf.shape[1]
                sl = jax.lax.dynamic_slice_in_dim(
                    rleaf.astype(pleaf.dtype), start, n * bs, axis=1)
                return pleaf.at[ids].set(
                    sl.reshape((n, bs) + pleaf.shape[2:]))
            return [{name: leaf(pl[name], rl[name]) for name in pl}
                    for pl, rl in zip(pool, row)]
        return jax.jit(wr, donate_argnums=tf._serving_donate(0))
    return tf._serving_jit(("paged_block_write", n), cfg, build)


def _jitted_gather_row(cfg, nb):
    """Gather `nb` pool blocks into a fresh [1, max_len] row cache
    (zero beyond nb*bs) — the admission-side prefix materialization:
    the suffix prefill attends over the shared prefix through this
    row, while the shared blocks themselves stay untouched in the
    pool."""
    def build(fz):
        def ga(pool, ids):
            def leaf(pleaf):
                bs = pleaf.shape[1]
                got = jnp.take(pleaf, ids, axis=0)
                got = got.reshape((1, nb * bs) + pleaf.shape[2:])
                full = jnp.zeros((1, fz.max_len) + pleaf.shape[2:],
                                 pleaf.dtype)
                return full.at[:, : nb * bs].set(got)
            return [{name: leaf(pl[name]) for name in pl}
                    for pl in pool]
        return jax.jit(ga)
    return tf._serving_jit(("paged_gather_row", nb), cfg, build)


def _jitted_table_row(cfg):
    """Replace lane i's whole block-table row (admission / park)."""
    return tf._serving_jit("paged_table_row", cfg, lambda fz: jax.jit(
        lambda tb, i, row: tb.at[i].set(row),
        donate_argnums=tf._serving_donate(0)))


def _jitted_table_entry(cfg):
    """Point one table entry at a freshly allocated block (the lazy
    per-dispatch extension)."""
    return tf._serving_jit("paged_table_entry", cfg, lambda fz: jax.jit(
        lambda tb, i, j, bid: tb.at[i, j].set(bid),
        donate_argnums=tf._serving_donate(0)))


# ---- speculative-decoding compiled programs ----------------------------
# Batched draft/verify/accept: each round proposes k tokens per lane,
# verifies every lane's [k+1] window in ONE ragged target pass
# (tf.verify_chunk / verify_chunk_paged), and rolls each lane forward by
# its own accepted count — per-lane acceptance is the _spec_core cumprod
# prefix-match, computed on device. Rejected cache entries heal by
# position exactly as the solo path documents (the next window starts at
# the first rejected position and rewrites everything it will attend).

# smoothing of the per-lane measured-acceptance EWMA the adaptive-k
# controller compares against MXNET_SPEC_ACCEPT_FLOOR
_SPEC_EWMA_ALPHA = 0.3

# the gap ledger's counters, in the order of a sync's sums
# (ContinuousBatcher._note_progress, _count_gaps)
_GAP_COUNTERS = ("serving.gaps", "serving.gap_ns",
                 "serving.gaps_behind_admit", "serving.gap_admit_ns")


def _ngram_propose(hist, tok, pos, keff, k, ng):
    """Prompt-lookup self-drafting (device-side, static-shape): for
    each lane, find the LATEST earlier occurrence of the ng-token
    suffix ending at the lane's current token and propose the k tokens
    that followed it — drawn from the lane's OWN stream history
    (`hist[b, :pos[b]+1]` is prompt + emissions, `hist[b, pos[b]] ==
    tok[b]`). No second model; repetitive text (code, quoted context,
    templated output) is where it pays. Lanes with no match, or a
    match whose continuation runs off the known stream, fall back to
    repeating the current token (right on runs, rejected otherwise —
    never a correctness question, the verify pass decides every
    emission). Draft slots at or past keff[b] are masked to the -1
    sentinel, which no vocab id equals — that is how the per-lane
    adaptive k shrinks the effective draft length inside one
    static-width program."""
    b, hl = hist.shape
    j = jnp.arange(hl)
    sidx = jnp.clip(pos[:, None] - (ng - 1) + jnp.arange(ng)[None],
                    0, hl - 1)
    suffix = jnp.take_along_axis(hist, sidx, axis=1)         # [B, ng]
    m = jnp.ones((b, hl), bool)
    for o in range(ng):                    # ng is tiny and static
        m = m & (jnp.roll(hist, -o, axis=1) == suffix[:, o:o + 1])
    # a candidate must END strictly before the suffix's own end — this
    # both excludes the trivial self-match and keeps roll()'s
    # wrap-around columns out of range
    valid = (j[None, :] + ng - 1) < pos[:, None]
    best = jnp.max(jnp.where(m & valid, j[None, :], -1), axis=1)
    gidx = best[:, None] + ng + jnp.arange(k)[None]          # [B, k]
    cand = jnp.take_along_axis(hist, jnp.clip(gidx, 0, hl - 1), axis=1)
    usable = (best[:, None] >= 0) & (gidx <= pos[:, None])
    drafts = jnp.where(usable, cand, tok[:, None])
    return jnp.where(jnp.arange(k)[None] < keff[:, None], drafts, -1)


def _jitted_spec_chunk(cfg, dcfg, k, ng, rounds, paged, use_model):
    """`rounds` speculative rounds as ONE compiled program — the
    dispatch unit of the speculative batcher, shaped like the
    pipelined chunk so the same in-flight window applies: the carry
    (cache/pool [+ draft cache/pool or n-gram history], lane tokens,
    positions) stays device-resident and is donated; the only outputs
    the host ever fetches are the per-round verified targets
    [rounds, B, k+1] and emit counts [rounds, B] (emit = accepted + 1:
    the verify logits always yield one token beyond the accepted
    prefix, so every round advances every lane — speculation can never
    be slower than stepping in tokens per dispatch). Greedy only; the
    batcher enforces that at construction."""
    kk = k + 1

    def build(fz):
        def accept(drafts, target):
            # _spec_core's acceptance, batched: count the matching
            # draft prefix per lane, emit it plus the one free token,
            # and the lane's new current token is target[acc]
            acc = jnp.cumprod(
                (drafts == target[:, :k]).astype(jnp.int32),
                axis=1).sum(axis=1)
            emit = acc + 1
            tok = jnp.take_along_axis(target, acc[:, None],
                                      axis=1)[:, 0]
            return emit, tok

        def hist_update(hist, target, emit, pos):
            # masked lane-buffer write: only the ACCEPTED window
            # prefix enters the stream history (positions past
            # max_len, and rejected slots, drop)
            rows = jnp.arange(hist.shape[0])[:, None]
            hpos = pos[:, None] + 1 + jnp.arange(kk)[None]
            keep = jnp.arange(kk)[None] < emit[:, None]
            safe = jnp.where(keep, hpos, fz.max_len + kk)
            return hist.at[rows, safe].set(target, mode="drop")

        # each provider's rounds are written once over (cache, tables),
        # `tables` None for the dense rows (tf._verify_chunk_on /
        # _decode_step_on pick the step); the dense programs keep their
        # own signature, without the tables
        if not use_model:
            def ngram_rounds(params, cache, tables, hist, tok, pos, keff):
                def body(carry, _):
                    cache, hist, tok, pos = carry
                    drafts = _ngram_propose(hist, tok, pos, keff, k, ng)
                    window = jnp.concatenate(
                        [tok[:, None], jnp.maximum(drafts, 0)], axis=1)
                    logits, cache = tf._verify_chunk_on(
                        params, cache, tables, window, pos, fz)
                    target = jnp.argmax(logits, axis=-1) \
                        .astype(jnp.int32)
                    emit, tok = accept(drafts, target)
                    hist = hist_update(hist, target, emit, pos)
                    return (cache, hist, tok, pos + emit), \
                        (target, emit)
                (cache, hist, tok, pos), (targets, emits) = \
                    jax.lax.scan(body, (cache, hist, tok, pos), None,
                                 length=rounds)
                return targets, emits, cache, hist, tok, pos
            if paged:
                chunk = ngram_rounds
                donate = tf._serving_donate(1, 3, 4, 5)
            else:
                def chunk(params, cache, hist, tok, pos, keff):
                    return ngram_rounds(params, cache, None, hist, tok,
                                        pos, keff)
                donate = tf._serving_donate(1, 2, 3, 4)
        else:
            # with a draft model the draft pool SHARES the target's
            # tables
            def model_rounds(params, dparams, cache, dcache, tables, tok,
                             pos, keff):
                def body(carry, _):
                    cache, dcache, tok, pos = carry
                    def dstep(c, i):
                        dc, t = c
                        dl, dc = tf._decode_step_on(
                            dparams, dc, tables, t, pos + i, dcfg)
                        nxt = jnp.argmax(dl, axis=-1) \
                            .astype(jnp.int32)
                        return (dc, nxt), nxt
                    (dcache, _), seq = jax.lax.scan(
                        dstep, (dcache, tok), jnp.arange(k))
                    drafts = jnp.where(
                        jnp.arange(k)[None] < keff[:, None],
                        seq.T, -1)
                    window = jnp.concatenate(
                        [tok[:, None], jnp.maximum(drafts, 0)], axis=1)
                    logits, cache = tf._verify_chunk_on(
                        params, cache, tables, window, pos, fz)
                    target = jnp.argmax(logits, axis=-1) \
                        .astype(jnp.int32)
                    emit, tok = accept(drafts, target)
                    return (cache, dcache, tok, pos + emit), \
                        (target, emit)
                (cache, dcache, tok, pos), (targets, emits) = \
                    jax.lax.scan(body, (cache, dcache, tok, pos), None,
                                 length=rounds)
                return targets, emits, cache, dcache, tok, pos
            if paged:
                chunk = model_rounds
                donate = tf._serving_donate(2, 3, 5, 6)
            else:
                def chunk(params, dparams, cache, dcache, tok, pos, keff):
                    return model_rounds(params, dparams, cache, dcache,
                                        None, tok, pos, keff)
                donate = tf._serving_donate(2, 3, 4, 5)
        return jax.jit(chunk, donate_argnums=donate)

    key = ("spec_chunk", k, ng, rounds, paged, use_model,
           dataclasses.astuple(dcfg) if use_model else None)
    return tf._serving_jit(key, cfg, build)


def _jitted_hist_row(cfg):
    """Replace lane i's stream-history row (the n-gram drafting state)
    at admission/requeue — the hist twin of the lane patch, sequenced
    after the in-flight dispatches like every carry patch."""
    return tf._serving_jit("spec_hist_row", cfg, lambda fz: jax.jit(
        lambda h, i, row: h.at[i].set(row),
        donate_argnums=tf._serving_donate(0)))


class BlockAllocator(object):
    """Free-list allocator with per-block refcounts over the paged KV
    pool. Block 0 is the reserved null block (unallocated table entries
    point at it) and is never handed out. A block mapped into several
    tables (shared prefix) carries one reference per mapping — prefix
    cache entry included — and returns to the free list only at
    refcount zero, so evicting one sharer can never free a block a
    live lane still reads.

    ``reserved`` tracks the worst-case FUTURE block demand of admitted
    requests: admission reserves its whole lifetime up front (that is
    the block-accounted capacity check), the lazy per-dispatch
    allocation converts reservation into real blocks as positions
    advance, and ``available`` (free minus reserved) is what admission
    and the router may still promise. A live request can therefore
    never stall on an empty free list.

    Under memory pressure the pool is ELASTIC (ISSUE 14):
    :meth:`shrink` moves free blocks onto a parked ledger — out of
    circulation, never below what ``reserved`` has already promised —
    and :meth:`grow` returns them; :meth:`extend` adds physically new
    block ids after the batcher grew the device pool. Parked blocks
    stay in the conservation law (pool == free + referenced + parked,
    the "reserved-aware" identity ``check_invariants`` asserts after
    every shrink/grow cycle)."""

    __slots__ = ("num_blocks", "ref", "reserved", "_free", "_parked")

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is null)")
        self.num_blocks = int(num_blocks)
        # pop() hands out low ids first
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self.ref = np.zeros((self.num_blocks,), np.int32)
        self.reserved = 0
        self._parked = []     # blocks taken out of circulation (shrink)

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def available(self):
        return len(self._free) - self.reserved

    @property
    def parked_blocks(self):
        return len(self._parked)

    def shrink(self, n):
        """Park up to ``n`` free blocks (out of circulation until
        :meth:`grow`). Never parks below the admission promise —
        ``reserved`` blocks stay deliverable — so a live request can
        still never stall on the free list. Returns the count actually
        parked."""
        take = max(min(int(n), len(self._free) - self.reserved), 0)
        for _ in range(take):
            self._parked.append(self._free.pop())
        return take

    def grow(self, n):
        """Unpark up to ``n`` blocks back onto the free list. Returns
        the count actually returned to circulation."""
        give = max(min(int(n), len(self._parked)), 0)
        for _ in range(give):
            self._free.append(self._parked.pop())
        return give

    def extend(self, n):
        """``n`` physically NEW block ids (the batcher just grew the
        device pool's block axis): widen the refcount array and free
        the fresh ids. Returns the new ids."""
        n = int(n)
        if n <= 0:
            return []
        ids = list(range(self.num_blocks, self.num_blocks + n))
        self.num_blocks += n
        self.ref = np.concatenate(
            [self.ref, np.zeros((n,), np.int32)])
        # front of the pop-from-end free list: fresh high ids hand out
        # LAST, keeping low-id locality for the common case
        self._free = ids[::-1] + self._free
        return ids

    def alloc(self, n):
        """n fresh blocks at refcount 1 (raises when the free list is
        short — callers gate on available/reserved, so this firing
        means an accounting bug, not load)."""
        if n > len(self._free):
            raise RuntimeError(
                "paged KV free list exhausted (%d requested, %d free) "
                "— admission accounting should have prevented this"
                % (n, len(self._free)))
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self.ref[b] = 1
        return ids

    def share(self, ids):
        """One more reference on each block (a new table mapping)."""
        for b in ids:
            if self.ref[b] < 1:
                raise RuntimeError("sharing unallocated block %d" % b)
            self.ref[b] += 1

    def release(self, ids):
        """Drop one reference per block; a block frees at zero."""
        for b in ids:
            self.ref[b] -= 1
            if self.ref[b] < 0:
                raise RuntimeError("double free of block %d" % b)
            if self.ref[b] == 0:
                self._free.append(b)

    def reserve(self, n):
        self.reserved += int(n)

    def unreserve(self, n):
        self.reserved -= int(n)
        assert self.reserved >= 0, "reservation accounting underflow"

    def check_invariants(self, mappings=None, quiesce=False):
        """Structural audit of the allocator — the standing leak/race
        detector every serving PR gets for free. Raises RuntimeError on
        the first violation, returns True otherwise.

        * conservation: every non-null block is EITHER on the free list
          (refcount 0) or referenced (refcount >= 1), never both, never
          neither — and the free list holds no duplicates.
        * ``mappings`` (optional): iterable of block-id lists (live lane
          tables + prefix-cache entries). Each block's refcount must
          equal the number of mappings that hold it, and no mapped
          block may sit on the free list.
        * ``reserved`` never exceeds the free list (``available >= 0``
          is the promise admission accounting makes).
        * pool conservation after every shrink/grow cycle:
          ``num_blocks - 1 == free + referenced + parked`` — parked
          blocks are disjoint from the free list, carry refcount 0,
          and hold no duplicates (the elastic ledger can neither leak
          nor double-count a block).
        * ``quiesce=True``: nothing live may remain — every block free
          or parked, every refcount zero, zero reservation (the
          zero-leak bar the overload harness asserts after a storm)."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise RuntimeError("free list holds duplicate block ids")
        parked = set(self._parked)
        if len(parked) != len(self._parked):
            raise RuntimeError("parked ledger holds duplicate block ids")
        if parked & free:
            raise RuntimeError(
                "blocks %s both parked and free" % sorted(parked & free))
        if 0 in free:
            raise RuntimeError("null block 0 leaked onto the free list")
        if 0 in parked:
            raise RuntimeError("null block 0 leaked onto the parked "
                               "ledger")
        if int(self.ref[0]) != 0:
            raise RuntimeError("null block 0 acquired a refcount")
        referenced = 0
        for b in range(1, self.num_blocks):
            r = int(self.ref[b])
            if b in parked:
                if r != 0:
                    raise RuntimeError(
                        "block %d is parked but refcount=%d" % (b, r))
                continue
            if b in free and r != 0:
                raise RuntimeError(
                    "block %d is free but refcount=%d" % (b, r))
            if b not in free and r < 1:
                raise RuntimeError(
                    "block %d leaked: refcount=%d and not free" % (b, r))
            if r >= 1:
                referenced += 1
        if len(free) + referenced + len(parked) != self.num_blocks - 1:
            raise RuntimeError(
                "pool conservation broken: %d free + %d referenced + "
                "%d parked != %d non-null blocks"
                % (len(free), referenced, len(parked),
                   self.num_blocks - 1))
        if self.reserved < 0:
            raise RuntimeError("negative reservation")
        if self.reserved > len(self._free):
            raise RuntimeError(
                "reserved %d exceeds free list %d — admission promised "
                "blocks that cannot be delivered"
                % (self.reserved, len(self._free)))
        if mappings is not None:
            want = {}
            for blocks in mappings:
                for b in blocks:
                    want[b] = want.get(b, 0) + 1
            for b, n in want.items():
                if b in free:
                    raise RuntimeError(
                        "mapped block %d sits on the free list" % b)
                if int(self.ref[b]) != n:
                    raise RuntimeError(
                        "block %d refcount=%d but %d mappings hold it"
                        % (b, int(self.ref[b]), n))
            for b in range(1, self.num_blocks):
                if int(self.ref[b]) > 0 and b not in want:
                    raise RuntimeError(
                        "block %d refcount=%d but no mapping holds it "
                        "(leak)" % (b, int(self.ref[b])))
        if quiesce:
            if self.reserved != 0:
                raise RuntimeError(
                    "quiesce with %d blocks still reserved"
                    % self.reserved)
            if len(self._free) + len(self._parked) \
                    != self.num_blocks - 1:
                raise RuntimeError(
                    "quiesce with %d of %d blocks leaked"
                    % (self.num_blocks - 1 - len(self._free)
                       - len(self._parked), self.num_blocks - 1))
        return True


class Request(object):
    __slots__ = ("rid", "tokens", "n_new", "emitted", "stop_token",
                 "seed", "priority", "key", "t_enq_ns", "t_admit_ns",
                 "t_first_ns", "t_last_ns", "admit_clock", "admit_seq",
                 "slo_bad")

    def __init__(self, rid, prompt, n_new, stop_token=None, seed=0,
                 priority=0, key=None):
        self.rid = rid
        self.tokens = list(prompt)   # prompt + generated so far
        self.n_new = n_new
        self.emitted = 0             # generated count
        self.stop_token = stop_token
        self.seed = seed             # sampling seed (requeue needs it)
        self.priority = int(priority)  # larger = more important
        self.key = key               # idempotency key (dedup window)
        # request-lifecycle clock (perf_counter_ns; None while no span
        # records): enqueue -> admit -> first token -> last host-visible
        # token, and the batcher's admission ledger as it stood at that
        # token (ContinuousBatcher._stamp)
        self.t_enq_ns = None
        self.t_admit_ns = None
        self.t_first_ns = None
        self.t_last_ns = None
        self.admit_clock = None
        self.admit_seq = None
        self.slo_bad = False         # any observation missed its SLO

    @property
    def done(self):
        """Budget exhausted, or the stop token was emitted (the stop
        token itself is part of the stream, like an EOS the client
        sees)."""
        if self.emitted >= self.n_new:
            return True
        return (self.stop_token is not None and self.emitted > 0
                and self.tokens[-1] == self.stop_token)


class ContinuousBatcher(object):
    """Slot-based continuous batching over a shared ragged decode step.

    >>> srv = ContinuousBatcher(params, cfg, max_batch=8)
    >>> rid = srv.admit([1, 2, 3], n_new=16)      # None when full
    >>> finished = srv.step()                     # {rid: [tokens...]}

    Decoding is greedy by default; pool-level temperature/top_k/top_p
    sample instead (generate()'s rule), with a PER-REQUEST seed at
    admit(). Either way a request's output is identical to its solo
    tf.generate() run — greedy argmax, or the same per-row key chain
    (tested).

    `chunk_size=k` runs k decode steps per dispatch in one device
    program (_jitted_pipeline_chunk) — multi-step scheduling for
    high-dispatch-latency links. Token streams are unchanged (tested
    chunked == unchunked == solo); what changes is granularity:
    admission and eviction happen at chunk boundaries, and a lane
    whose request ends mid-chunk idles for the remainder.

    `cache_prefix(tokens)` prefills a shared prefix once (system
    prompt, few-shot preamble); admissions whose prompt starts with a
    cached prefix prefill only the suffix. LRU-bounded
    (prefix_cache_slots row caches on device).

    `pipeline_depth=d` (default 2) is the WIDTH of step()'s window:
    up to d chunk dispatches ride in flight against the
    device-resident carry (cache, lane tokens/positions, sample keys),
    and each step() syncs only the OLDEST chunk's emissions — so at
    d >= 2 the next round is on the device before the last one's
    tokens are read, and the host's share of a round does not leave
    the device idle (on the chip 3-4 ms of every round at a window of
    one; depth 3 served no more tokens/s than 2 and a worse tail:
    docs/SERVING.md). Admissions and evictions are tiny jitted lane
    patches applied to the carry between dispatches (bounded
    staleness at d >= 2: a token is returned d - 1 step()s after the
    one that dispatched its chunk; a request admitted while chunks
    are in flight enters at the NEXT dispatch boundary; chunks already
    in flight keep advancing its lane's previous occupant, whose
    emissions are discarded by request identity at sync). Token
    streams are bit-identical at every width and to solo generate()
    (tested). `pipeline_depth=1` is a window of one on the same loop:
    step() dispatches one chunk and syncs it before it returns, for a
    caller who needs a round's tokens in the step() that computed
    them.

    `paged=True` (default: MXNET_KV_PAGED) virtualizes the cache into
    fixed-size blocks (`block_size`, default MXNET_KV_BLOCK_SIZE=16):
    one per-layer pool of `num_blocks` blocks replaces the per-lane
    dense rows, each lane maps positions through an int32 block table,
    and capacity decouples from max_len — admission accounts in BLOCKS
    (the request's prompt + n_new worst-case demand must fit the free
    list) instead of assuming every lane owns a [max_len] row, so a
    pool sized for B dense lanes admits far more mixed-length
    requests. Blocks allocate lazily as positions advance (against an
    admission-time reservation, so a live lane never stalls) and free
    on finish/evict. `cache_prefix` becomes REFCOUNTED BLOCK SHARING:
    an admitted prompt starting with a cached prefix maps the prefix's
    full blocks into its table (stored once, copy-on-extend for the
    partial tail), and a shared block frees only at refcount zero.
    Streams stay bit-exact vs solo generate() — the gathered view
    feeds the identical attention contraction — and int8-KV, GQA,
    chunking, pipelining, and dispatch-failure requeue all compose.

    `spec_k=k` (default: MXNET_SPEC_K) turns every decode round into a
    SPECULATIVE draft/verify dispatch: k drafted tokens per lane —
    n-gram prompt-lookup over the lane's own stream by default
    (spec_ngram / MXNET_SPEC_NGRAM suffix length), or a small draft
    model when (draft_params, draft_cfg) are given — verified by one
    ragged [B, k+1] target pass with device-side acceptance, so each
    lane advances 1..k+1 tokens per target dispatch. Composes with
    chunking (chunk_size rounds per dispatch), pipelining (depth
    speculative dispatches in flight), and paging (tables advance by
    accepted counts; worst-case draft blocks are released at sync).
    spec_accept_floor > 0 (MXNET_SPEC_ACCEPT_FLOOR) enables the
    per-lane adaptive-k controller: a lane whose measured-acceptance
    EWMA drops below the floor drafts one token fewer next round
    (never below 1), and recovers one at a time while at/above it.
    Greedy-only; streams stay bit-exact vs solo generate() (tested
    across providers, paging, and depths). With spec_k unset nothing
    here runs — behavior AND dispatch count are unchanged (tested).

    `name` labels this replica's chaos site (serving.dispatch.<name>)
    so fleet tests can kill one replica of a router pool
    deterministically."""

    def __init__(self, params, cfg, max_batch=8, greedy=None,
                 temperature=1.0, top_k=None, top_p=None,
                 chunk_size=1, prefix_cache_slots=4, pipeline_depth=2,
                 paged=None, block_size=None, num_blocks=None,
                 name=None, spec_k=None, spec_ngram=None,
                 spec_accept_floor=None, draft_params=None,
                 draft_cfg=None, brownout=None, brownout_attain=None,
                 brownout_trip=None, brownout_clear=None,
                 journal=None):
        t_build = time.perf_counter_ns()     # span startup.batcher
        if cfg.max_len < 8:
            raise ValueError("max_len too small for the bucket floor")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.params = params
        self.cfg = cfg
        self.max_batch = int(max_batch)
        # generate()'s rule, incl. greedy=False for pure ancestral
        # sampling (temperature=1.0 alone would read as greedy)
        sampling_requested = (temperature != 1.0 or top_k is not None
                              or top_p is not None)
        if greedy is None:
            greedy = not sampling_requested
        elif greedy and sampling_requested:
            raise ValueError(
                "greedy=True ignores temperature/top_k/top_p — pass "
                "greedy=False (or omit greedy) to sample")
        self.greedy = greedy
        self.chunk_size = int(chunk_size)
        self.pipeline_depth = int(pipeline_depth)
        self.name = name
        self._chaos_site = ("serving.dispatch" if name is None
                            else "serving.dispatch.%s" % name)
        self._controls = (self.greedy, float(temperature), top_k, top_p)
        # speculative dispatches (spec_k drafts verified per round)
        if spec_k is None:
            v = _fastenv.get("MXNET_SPEC_K")
            spec_k = int(v) if v else None
        self.spec_k = int(spec_k) if spec_k else None
        self._spec_on = self.spec_k is not None
        if self._spec_on:
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if not self.greedy:
                raise ValueError(
                    "speculative dispatches are greedy-only: the "
                    "accept test compares drafts against the target "
                    "argmax (drop spec_k to sample)")
            if spec_ngram is None:
                v = _fastenv.get("MXNET_SPEC_NGRAM")
                spec_ngram = int(v) if v else 2
            self.spec_ngram = int(spec_ngram)
            if self.spec_ngram < 1:
                raise ValueError("spec_ngram must be >= 1")
            if spec_accept_floor is None:
                v = _fastenv.get("MXNET_SPEC_ACCEPT_FLOOR")
                spec_accept_floor = float(v) if v else 0.0
            self.spec_accept_floor = float(spec_accept_floor)
            if (draft_params is None) != (draft_cfg is None):
                raise ValueError(
                    "draft_params and draft_cfg come as a pair")
            if draft_cfg is not None:
                if draft_cfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        "draft vocab %d != target vocab %d"
                        % (draft_cfg.vocab_size, cfg.vocab_size))
                if draft_cfg.max_len < cfg.max_len:
                    raise ValueError(
                        "draft max_len %d < target max_len %d — the "
                        "draft cache shares the target's lane "
                        "positions"
                        % (draft_cfg.max_len, cfg.max_len))
            self.draft_params = draft_params
            self.draft_cfg = draft_cfg
            self._spec_provider = ("model" if draft_params is not None
                                   else "ngram")
        elif draft_params is not None or draft_cfg is not None:
            raise ValueError("a draft model without spec_k does "
                             "nothing — set spec_k (or MXNET_SPEC_K)")
        else:
            self.spec_ngram = None
            self.spec_accept_floor = 0.0
            self.draft_params = self.draft_cfg = None
            self._spec_provider = None
        # target-model dispatches issued (chunks and speculative
        # rounds' verify passes all count one per device
        # dispatch) — the denominator of dispatches-per-token, and the
        # off-path-silence invariant tests pin spec_k=None against
        self.dispatch_count = 0
        if paged is None:
            paged = (_fastenv.get("MXNET_KV_PAGED") or "") \
                not in ("", "0", "false", "False")
        self.paged = bool(paged)
        # only dense lanes carry a recurrent state, latent rows or a
        # window layer's ring (tf._DENSE_ONLY): a block holds K/V
        # positions of heads and a state has none to live in or be
        # shared through, nor is a block freed behind a window; a
        # rejected draft is already folded into a state, or has
        # overwritten a ring's oldest rows, and cannot be rolled
        # back; the int8 layout has no place for any of them
        for on, what in ((self.paged, "paged=True (or MXNET_KV_PAGED)"),
                         (self._spec_on, "spec_k (or MXNET_SPEC_K)"),
                         (cfg.kv_cache_int8, "kv_cache_int8")):
            if on:
                tf._refuse_dense_only(cfg, "a batcher with " + what)
        if self.paged:
            if block_size is None:
                block_size = int(_fastenv.get("MXNET_KV_BLOCK_SIZE",
                                              DEFAULT_KV_BLOCK_SIZE))
            self.block_size = int(block_size)
            if self.block_size < 1 \
                    or cfg.max_len % self.block_size:
                raise ValueError(
                    "block_size %d must divide max_len %d (set "
                    "MXNET_KV_BLOCK_SIZE accordingly)"
                    % (self.block_size, cfg.max_len))
            self._nb = cfg.max_len // self.block_size   # table width
            if num_blocks is None:
                # dense-equivalent HBM budget by default: every lane
                # could still hold a full-context row (+ the null block)
                num_blocks = self.max_batch * self._nb + 1
            self.num_blocks = int(num_blocks)
            self._alloc = BlockAllocator(self.num_blocks)
            if _membudget.enabled():
                # pool init is the one serving allocation whose size is
                # known analytically before any program compiles —
                # preflight it against live headroom like a jit boundary
                _membudget.preflight_bytes(
                    "serving.paged_pool",
                    tf.paged_cache_nbytes(cfg, self.num_blocks,
                                          self.block_size),
                    signature="%dx%d" % (self.num_blocks,
                                         self.block_size))
            self._pool = tf.init_paged_cache(cfg, self.num_blocks,
                                             self.block_size)
            self._tables = jnp.zeros((self.max_batch, self._nb),
                                     jnp.int32)
            self._lane_blocks = [[] for _ in range(self.max_batch)]
            self._lane_need = [0] * self.max_batch
            self._cache = None
        else:
            self._cache = tf.init_cache(cfg, self.max_batch)
        # the host's mirror of the carry's positions: the scheduled
        # position a lane = the device's after every dispatched chunk
        # (the carry itself is never synced; a speculative dispatch's
        # worst case until its sync reconciles it), patched with the
        # carry and advanced at every dispatch. It drives the lazy
        # pre-dispatch block allocation and the count of latent rows
        # fetched
        self._pos = np.zeros((self.max_batch,), np.int32)
        self._slots = [None] * self.max_batch   # Request or None
        # what a live lane holds, for the serving.state_bytes /
        # serving.kv_bytes gauges: bytes of recurrent state a lane
        # (whatever its length: a Mamba or Mamba-2 layer's, a KDA
        # layer's matrices) and, for every layer that keeps rows (K/V
        # heads, or a latent; an "ffn" block keeps nothing), the rows
        # its leaf holds a lane (max_len, or a window layer's ring) with
        # the bytes of one
        self._lane_row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
        row = list(zip(tf._layer_kinds(cfg), self._lane_row))

        def nbytes(layer):
            return sum(x.size * x.dtype.itemsize for x in layer.values())
        self._latent_layers = sum(kind == "mla" for kind, _ in row)
        self._ssd_layers = sum(kind == "mamba2" for kind, _ in row)
        self._lane_state_bytes = sum(
            nbytes(l) for kind, l in row if kind in tf._RECURRENT)
        rowed = [(kind, layer) for kind, layer in row
                 if kind not in tf._RECURRENT and layer]
        held = [(next(iter(layer.values())).shape[1], nbytes(layer),
                 kind == "window") for kind, layer in rowed]
        # rows of every such leaf, beside the block decode's contraction
        # fetches them in where it is kernels/kv_decode.py (None: the
        # XLA text, the whole leaf; tf.kv_decode_block, the call's own
        # rule); bytes a position of those max_len long; (rows, bytes of
        # one) of each ring
        self._leaf_rows = [rows for rows, _, _ in held]
        self._leaf_blocks = [
            tf.kv_decode_block(cfg, layer["k"]) if kind == "attention"
            else None for kind, layer in rowed]
        self._kv_layers = sum(kind in ("attention", "window")
                              for kind, _ in row)
        self._kv_pos_bytes = sum(size // rows for rows, size, ring in held
                                 if not ring)
        self._rings = [(rows, size // rows) for rows, size, ring in held
                       if ring]
        # the device-resident lane carry: tok/pos/keys live on device
        # between dispatches, so a chunk dispatch uploads nothing and a
        # chunk sync downloads only the [k, B] emissions
        self._dev_tok = jnp.zeros((self.max_batch,), jnp.int32)
        self._dev_pos = jnp.zeros((self.max_batch,), jnp.int32)
        self._dev_keys = jnp.zeros((self.max_batch, 2), jnp.uint32)
        # in-flight dispatches, oldest first: (emissions [k, B],
        # per-lane rid snapshot at dispatch time, the chunk's
        # routing counts or None, the positions it was given) —
        # speculative records carry (targets, emits, rids, keff)
        # instead
        self._inflight = deque()
        # the program and step()'s (dispatch, sync) pair, resolved
        # once — a dispatch must not pay the _serving_jit registry
        # lookup per chunk. The pair is the class's plain functions,
        # called with self: a bound method kept on the instance would
        # be a reference cycle, and the lanes' device memory would wait
        # for a collector pass instead of the last reference
        cls = type(self)
        if self._spec_on:
            self._spec_fn = _jitted_spec_chunk(
                cfg, self.draft_cfg, self.spec_k,
                self.spec_ngram, self.chunk_size, self.paged,
                self._spec_provider == "model")
            self._round = cls._dispatch_spec, cls._sync_oldest_spec
        else:
            self._pipe_fn = _jitted_pipeline_chunk(
                cfg, *self._controls, self.chunk_size, self.paged)
            self._round = cls._dispatch_chunk, cls._sync_oldest
        self._patch_fn = _jitted_lane_patch(cfg)
        if self._spec_on:
            # per-lane adaptive k: effective draft length (masked
            # inside the static-width program) and the measured
            # acceptance EWMA the floor controller reads
            self._keff = np.full((self.max_batch,), self.spec_k,
                                 np.int32)
            self._accept_ewma = np.ones((self.max_batch,), np.float64)
            self._spec_rounds = 0
            self._spec_drafted = 0
            self._spec_accepted = 0
            if self._spec_provider == "ngram":
                self._dev_hist = jnp.zeros(
                    (self.max_batch, cfg.max_len), jnp.int32)
                self._hist_fn = _jitted_hist_row(cfg)
            elif self.paged:
                # the draft pool SHARES the target's block tables: one
                # table row covers both models' positions, so block
                # accounting stays single-ledger (the cost: prefix
                # sharing is disabled — cached blocks hold target K/V
                # only; see admit()/cache_prefix)
                self._dpool = tf.init_paged_cache(
                    self.draft_cfg, self.num_blocks, self.block_size)
            else:
                self._dcache = tf.init_cache(self.draft_cfg,
                                             self.max_batch)
        # dispatch-failure recovery: a failed decode dispatch frees the
        # lanes and requeues the live requests (greedy streams resume
        # bit-exactly) instead of wedging the batcher; consecutive
        # failures past the cap re-raise — a deterministic fault must
        # not become a silent requeue loop
        self._dispatch_failures = 0
        self._max_dispatch_failures = 3
        self._next_rid = 0
        # goodput accounting: completed (delivered) tokens since the
        # first admission — feeds the serving.goodput_tok_s gauge
        self._completed_tokens = 0
        self._t_serve_start_ns = None
        # the gap ledger, kept while spans record: the summed duration
        # and the count of the admit() / admit_continuation() calls that
        # admitted a request. Every request is stamped with both beside
        # its last token's time, so a delivery knows whose admissions it
        # waited behind (_note_admit, _note_progress)
        self._admit_clock_ns = 0
        self._admit_seq = 0
        self._ledger_on = False
        # weight-version identity (integrity.tree_fingerprint over the
        # served params) — lazily computed once, cached: replicas of
        # one fleet must agree, and the router checks they do
        self._weight_fp = None
        if _obs.enabled():
            _obs_http.maybe_start()    # MXNET_OBS_HTTP live scrape
        # prefix cache, LRU-bounded (prefix_cache_slots). Dense mode:
        # tuple(tokens) -> (row_cache, last_row_logits) — one [1,
        # max_len] row cache on device per entry. Paged mode:
        # tuple(tokens) -> (block_ids, last_row_logits) — the prefix
        # lives IN the pool, refcounted, and admissions map its full
        # blocks instead of copying them
        self._prefix_cache = {}
        self._prefix_slots = int(prefix_cache_slots)
        # KV-pressure preemption: admit(priority=...) may evict a
        # strictly lower-priority lane to cover a block shortfall; the
        # victim lands here as (Request, preempt_ns) for the caller
        # (router._admit_queued, or run()) to resume bit-exactly via
        # admit_continuation()
        self.preempted = []
        # brownout ladder (MXNET_SERVING_BROWNOUT=1): rung 0 is
        # healthy; sustained SLO-attainment drop, block exhaustion, or
        # (membudget-armed) device-headroom starvation climbs one rung
        # at a time — 1: clamp the speculative draft width, 2: stop
        # admitting new shareable prefixes, 3: throttle admission to
        # one per scheduling round, 4: kv_shrink — park part of the KV
        # pool (returned on the walk back down), 5: shed the lowest
        # priority class — and sustained recovery walks back down
        # (hysteresis: the trip and clear streaks differ)
        if brownout is None:
            brownout = (_fastenv.get("MXNET_SERVING_BROWNOUT") or "") \
                not in ("", "0", "false", "False")
        self.brownout = bool(brownout)
        if brownout_attain is None:
            v = _fastenv.get("MXNET_SERVING_BROWNOUT_ATTAIN")
            brownout_attain = float(v) if v else 0.9
        self._brownout_attain = float(brownout_attain)
        if brownout_trip is None:
            v = _fastenv.get("MXNET_SERVING_BROWNOUT_TRIP")
            brownout_trip = int(v) if v else 3
        self._brownout_trip = int(brownout_trip)
        if brownout_clear is None:
            v = _fastenv.get("MXNET_SERVING_BROWNOUT_CLEAR")
            brownout_clear = int(v) if v else 8
        self._brownout_clear = int(brownout_clear)
        self._bo_rung = 0
        self._bo_bad = 0
        self._bo_good = 0
        self._round_admits = 0
        # blocks the kv_shrink rung parked (returned when it clears)
        self._bo_parked = 0
        # MXNET_SERVING_DEBUG=1: allocator invariants audited at every
        # idle point (cheap standing leak detector; tests call
        # check_invariants unconditionally)
        self._debug = (_fastenv.get("MXNET_SERVING_DEBUG") or "") \
            not in ("", "0", "false", "False")
        # request write-ahead journal (models/journal.py): every
        # admission / synced emission / preemption / finish appends a
        # CRC-guarded record, and recover() replays it after a crash.
        # journal=None reads MXNET_SERVING_JOURNAL_DIR (a NAMED replica
        # journals into a per-replica subdirectory, so an in-process
        # fleet's segments never collide); journal=False is off even
        # with the env set (the router journals for its fleet instead);
        # a str is a directory; a RequestJournal is used as-is. Off is
        # one guarded branch per hook — dispatch count and numerics are
        # bit-identical with the journal unset (tested).
        if journal is None:
            jd = _fastenv.get("MXNET_SERVING_JOURNAL_DIR")
            if jd and name is not None:
                jd = os.path.join(jd, name)
            journal = _journal.RequestJournal(jd) if jd else False
        elif isinstance(journal, str):
            journal = _journal.RequestJournal(journal)
        self._journal = journal or None
        # idempotency dedup window: key -> live rid, and key ->
        # (rid, final tokens) once finished; a duplicate submit returns
        # the ORIGINAL rid (serving.dedup_hits counts them) and a
        # finished duplicate re-delivers through _pending_finished
        self._idem = {}
        self._idem_done = {}
        # results to deliver at the next step() without a dispatch:
        # dedup re-deliveries and streams drained by swap_weights()
        self._pending_finished = {}
        if _obs.enabled():
            # flight recorder: incident bundles carry this replica's
            # health snapshot (weak-ref'd — the recorder never pins a
            # dead batcher); the time-series sampler daemon starts once
            # per process, shared by every replica
            _flight.register_context(
                "serving.%s" % (self.name or "batcher"),
                self.health_snapshot)
            _timeseries.maybe_start()
        # cache rows, lane state, the carry's constructors
        _obs.record_startup("startup.batcher", t_build)

    # ---- admission ----

    @property
    def active_count(self):
        return sum(1 for s in self._slots if s is not None)

    @property
    def has_capacity(self):
        """A free lane — and, under paging, at least one block of
        unpromised capacity (free minus reservations, counting
        evictable prefix entries): admission accounts in BLOCKS, so a
        pool can be full long before its lanes are (and vice versa).
        The per-request check is admit() itself — a specific prompt's
        worst-case demand can still exceed one free block."""
        if self.active_count >= self.max_batch:
            return False
        if self.paged:
            return self._alloc.available >= 1 \
                or bool(self._prefix_cache)
        return True

    @property
    def free_blocks(self):
        """Unallocated pool blocks (None when not paged) — the router's
        primary load signal."""
        return self._alloc.free_blocks if self.paged else None

    @property
    def weight_fingerprint(self):
        """8-hex id of the served weights (one
        ``integrity.tree_fingerprint`` call, cached — the params are
        immutable for the batcher's lifetime). The same id appears in
        checkpoint manifests (``param_fingerprint``), so an operator
        can trace exactly which checkpoint a replica serves; the
        router compares it across the fleet. Also published as the
        ``serving.weight_version`` gauge (the id as an integer —
        < 2^32, exact in a float64) for /healthz scrapers."""
        if self._weight_fp is None:
            self._weight_fp = _integrity.params_fingerprint(self.params)
            if _obs.enabled():
                _obs.gauge("serving.weight_version").set(
                    int(self._weight_fp, 16))
        return self._weight_fp

    def health_snapshot(self):
        """The per-replica routing signals, /healthz-shaped (same names
        a scraper reads off MXNET_OBS_HTTP's /healthz `counters`):
        lane occupancy, paged-pool headroom, rolling SLO attainment,
        the weight-version fingerprint, and under ``"startup"`` what
        the process has spent building programs so far (the compile
        ledger's ``recompile.summary()``: always kept).
        models/router.py polls this for in-process replicas; a
        multi-process fleet scrapes the HTTP endpoint instead."""
        active = self.active_count
        snap = {
            "serving.lane_occupancy": active,
            "serving.lane_utilization": active / float(self.max_batch),
            "serving.state_bytes": active * self._lane_state_bytes,
            "serving.kv_bytes": self._kv_bytes(),
            "serving.slo_attainment": _slo.attainment(),
            "serving.weight_fingerprint": self.weight_fingerprint,
            "serving.weight_version": int(self.weight_fingerprint, 16),
            "startup": _obs_recompile.summary(),
        }
        if self._journal is not None:
            snap["serving.journal_depth_bytes"] = \
                self._journal.depth_bytes
            snap["serving.journal_lag_records"] = \
                self._journal.lag_records
        if self.paged:
            usable = self.num_blocks - 1
            snap["serving.kv_free_blocks"] = self._alloc.free_blocks
            snap["serving.kv_available_blocks"] = self._alloc.available
            snap["serving.kv_block_utilization"] = \
                (usable - self._alloc.free_blocks) / float(usable)
            if self._alloc.parked_blocks:
                snap["serving.kv_parked_blocks"] = \
                    self._alloc.parked_blocks
        if _membudget.armed():
            # live device headroom (None on platforms without memory
            # stats): the router's starvation gate stops admitting to
            # a replica whose headroom fell below the reserve
            hb = _membudget.headroom_bytes()
            if hb is not None:
                snap["mem.headroom_bytes"] = hb
        if self._spec_on:
            snap["serving.spec_draft_ratio"] = (
                self._spec_accepted / self._spec_drafted
                if self._spec_drafted else 1.0)
            snap["serving.spec_k_live"] = float(np.mean(self._keff))
        if self.brownout:
            snap["serving.brownout_rung"] = self._bo_rung
        if self.cfg.n_experts:
            # counted while spans record (_count_expert_matmuls)
            for name in ("moe.grouped_kernel", "moe.grouped_reference"):
                snap[name] = _obs.counter(name).value
        if self._latent_layers:
            # counted while spans record (_count_row_stores)
            for name in ("mla.row_store_kernel", "mla.row_store_scatter"):
                snap[name] = _obs.counter(name).value
        if self._kv_layers:
            # counted while spans record (_count_kv_contractions)
            for name in ("kv.decode_kernel", "kv.decode_reference"):
                snap[name] = _obs.counter(name).value
        if self._kv_layers:
            # counted while spans record (_count_prefill): one the whole
            for name in ("attn.chunk_calls", "attn.chunk_kernel"):
                snap[name] = _obs.counter(name).value
        if self._kv_layers or self._latent_layers:
            # counted as a program is traced (tf._causal_attention): the
            # whole-prompt prefill of generate() and the training forward
            # in this process, which the batcher's own programs never run
            for name in ("attn.causal_kernel", "attn.causal_reference"):
                snap[name] = _obs.counter(name).value
        if self._rings:
            # counted while spans record (_count_kv_rows)
            for name in ("kv.rows_read", "kv.rows_live", "kv.rows_ring"):
                snap[name] = _obs.counter(name).value
        return snap

    def _kv_bytes(self):
        """Bytes of rows the live lanes hold (the serving.kv_bytes
        gauge): a position's worth a token in every layer that keeps
        rows, a window layer's ring counted at its own rows."""
        held = [len(r.tokens) for r in self._slots if r is not None]
        return self._kv_pos_bytes * sum(held) + sum(
            min(n, rows) * each for n in held for rows, each in self._rings)

    def check_invariants(self, quiesce=False):
        """Audit paged block accounting against every live mapping —
        lane tables plus prefix-cache entries (see
        BlockAllocator.check_invariants). ``quiesce=True`` additionally
        demands zero live lanes, an empty prefix cache's worth of
        references released, and a whole free list — the zero-leak bar.
        A no-op (True) when not paged."""
        if not self.paged:
            return True
        mappings = [b for b in self._lane_blocks if b]
        mappings += [blocks for blocks, _ in
                     self._prefix_cache.values()]
        self._alloc.check_invariants(
            mappings=mappings,
            quiesce=quiesce and not self._prefix_cache)
        if quiesce and self.active_count:
            raise RuntimeError(
                "quiesce with %d live requests" % self.active_count)
        for i, req in enumerate(self._slots):
            if req is None and self._lane_blocks[i]:
                raise RuntimeError(
                    "freed lane %d still maps %d blocks"
                    % (i, len(self._lane_blocks[i])))
            if req is None and self._lane_need[i]:
                raise RuntimeError(
                    "freed lane %d still reserves toward a %d-block "
                    "lifetime" % (i, self._lane_need[i]))
        return True

    def _debug_idle_check(self):
        """The MXNET_SERVING_DEBUG=1 idle-point audit: whenever the
        pool drains, the allocator must balance (every future serving
        change inherits this leak detector for free)."""
        if self._debug and self.paged and self.active_count == 0:
            self.check_invariants()

    # ---- paged block accounting ----

    def _block_math(self, t_p, total_len):
        """(lifetime_blocks, init_blocks) for a request whose final
        stream is `total_len` tokens from a `t_p`-token prompt: the
        deepest cache write of its life is position total_len - 2 (the
        final emitted token is never written), and admission must also
        cover position t_p — the first decode write target."""
        last_pos = max(t_p, total_len - 2)
        return (last_pos // self.block_size + 1,
                t_p // self.block_size + 1)

    def _evict_prefixes(self, demand, keep=None):
        """LRU-evict cached prefixes until `demand` blocks are
        available (or nothing evictable remains). Released blocks hit
        the free list only at refcount zero, so an entry shared with
        live lanes yields nothing until they finish — which is exactly
        the safety the refcount exists for. `keep` shields the entry
        the in-progress admission is about to share."""
        while self._alloc.available < demand:
            victim = next(
                (k for k in self._prefix_cache if k != keep
                 and any(self._alloc.ref[b] == 1
                         for b in self._prefix_cache[k][0])),
                None)                  # oldest evictable first (LRU);
            if victim is None:         # an entry pinned by live lanes
                return False           # would free nothing — skip it
            blocks, _ = self._prefix_cache.pop(victim)
            self._alloc.release(blocks)
            if _obs.enabled():
                _obs.record_instant(
                    "serving.prefix_evict", cat="serving",
                    args={"prefix_len": len(victim),
                          "blocks": len(blocks)})
        return True

    def _lookup_prefix_blocks(self, prompt):
        """Paged twin of _lookup_prefix: longest cached prefix ->
        (p_len, block_ids, last_row_logits), LRU-refreshed; (0, [],
        None) on a miss. The blocks stay refcounted by the entry —
        admission adds its own reference per shared FULL block."""
        best = None
        for key in self._prefix_cache:
            if len(key) <= len(prompt) \
                    and tuple(prompt[:len(key)]) == key:
                if best is None or len(key) > len(best):
                    best = key
        if best is None:
            return 0, [], None
        hit = self._prefix_cache.pop(best)
        self._prefix_cache[best] = hit               # LRU refresh
        return len(best), hit[0], hit[1]

    def cache_prefix(self, tokens):
        """Prefill `tokens` once and keep the row cache + last-row
        logits for reuse: a later admit() whose prompt starts with
        these tokens prefills only the suffix (system prompts,
        few-shot preambles — the shared-prefix serving pattern).
        The prefix is processed at its exact length (no bucket pad),
        so the cached row holds zeros beyond it and nothing stale is
        ever attendable. Entries are LRU-bounded by
        prefix_cache_slots; each holds one full-width row cache on
        device. Returns the prefix length."""
        if self._prefix_slots < 1:
            raise ValueError("prefix caching disabled "
                             "(prefix_cache_slots=0)")
        if self.paged and self._spec_on \
                and self._spec_provider == "model":
            raise ValueError(
                "prefix sharing is unavailable with a paged draft "
                "model: cached blocks hold target K/V only, and the "
                "draft pool rides the same block tables (use the "
                "n-gram provider, or dense caching)")
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if not toks:
            raise ValueError("empty prefix")
        if len(toks) >= self.cfg.max_len:
            raise ValueError("prefix %d must leave room under "
                             "max_len %d" % (len(toks),
                                             self.cfg.max_len))
        if self.paged:
            return self._cache_prefix_paged(toks)
        key = tuple(toks)
        hit = self._prefix_cache.pop(key, None)
        if hit is None:
            logits, row_cache = self._prefill_rows(
                self._fresh_row(), toks, 0, pad=False)
            hit = (row_cache, logits)
        self._prefix_cache[key] = hit                # insert/refresh
        while len(self._prefix_cache) > self._prefix_slots:
            self._prefix_cache.pop(next(iter(self._prefix_cache)))
        return len(toks)

    def _cache_prefix_paged(self, toks):
        """Paged cache_prefix: the prefix is prefilled once into POOL
        blocks (refcount 1 held by the cache entry) and shared by
        admissions at block granularity. A nested shorter prefix's
        full blocks are themselves shared into the new entry — nesting
        costs only the tail. LRU-bounded like the dense path, except
        the bound (and block pressure from admissions) releases
        references, not device rows."""
        key = tuple(toks)
        hit = self._prefix_cache.pop(key, None)
        if hit is not None:
            self._prefix_cache[key] = hit            # LRU refresh
            return len(toks)
        p = len(toks)
        bs = self.block_size
        # share a nested cached prefix's full blocks, if any
        p_sub, sub_blocks, _ = self._lookup_prefix_blocks(toks)
        s = p_sub // bs
        nb = (p + bs - 1) // bs
        own_n = nb - s
        if own_n > self._alloc.available \
                and not self._evict_prefixes(
                    own_n, keep=tuple(toks[:p_sub]) if p_sub else None):
            raise RuntimeError(
                "no free KV blocks for a %d-token prefix (%d needed, "
                "%d available)" % (p, own_n, self._alloc.available))
        if p_sub:
            nb_sub = (p_sub + bs - 1) // bs
            row = _jitted_gather_row(self.cfg, nb_sub)(
                self._pool, jnp.asarray(sub_blocks[:nb_sub], jnp.int32))
        else:
            row = self._fresh_row()
        # exact-length suffix prefill (no bucket pad): the cached
        # blocks hold zeros beyond the prefix, so nothing stale is
        # ever attendable through a sharer's table
        logits, row = self._prefill_rows(row, toks[p_sub:], p_sub,
                                         pad=False)
        own = self._alloc.alloc(own_n)
        if s:
            self._alloc.share(sub_blocks[:s])
        self._pool = _jitted_block_write(self.cfg, own_n)(
            self._pool, row, jnp.asarray(own, jnp.int32),
            jnp.int32(s * bs))
        self._prefix_cache[key] = (sub_blocks[:s] + own, logits)
        while len(self._prefix_cache) > self._prefix_slots:
            old = next(iter(self._prefix_cache))
            blocks, _ = self._prefix_cache.pop(old)
            self._alloc.release(blocks)
        if _obs.enabled():
            self._publish_occupancy()
        return p

    def _prefill_rows(self, row_cache, tokens, start, pad=True):
        """`tokens` into the one-lane `row_cache` at positions `start`
        onward, in calls of prefill_widths' widths (one compiled
        prefill a width; `pad` False = the rest at its exact length, for
        a row others will share) through the one program, prefill_chunk
        with a logits row. Rows behind the last real token are the
        bucket's padding: K/V or latent rows there are overwritten by
        decode before attention can reach them, and a recurrent state
        stops at the logits row (prefill_chunk) and continues from the
        cache's own: zeros, a cached prefix's, or the chunk's before.
        Returns (the last token's logits [1, vocab], row_cache)."""
        fn = tf._jitted_prefill_chunk_row(self.cfg)
        at = 0
        for width in prefill_widths(self.cfg, len(tokens), start, pad):
            part = tokens[at: at + width]
            padded = np.zeros((1, width), np.int32)
            padded[0, : len(part)] = part
            logits, row_cache = fn(
                self.params, row_cache, jnp.asarray(padded),
                jnp.int32(start + at), jnp.int32(len(part) - 1))
            self._count_prefill(len(part), width)
            at += width
        return logits, row_cache

    def _count_prefill(self, tokens, rows):
        """While spans record: one prefill_chunk call of an admission
        into the counters serving.prefill_tokens (the prompt's real
        tokens it took in) and serving.prefill_rows (the rows it
        computed, the bucket's padding included); for a model with
        Mamba-2 layers also ssd.rows_live, those tokens a Mamba-2
        layer, and ssd.rows_scanned, the rows its chunked form ran for
        them: the call's width in whole chunks (ssd.mixer_seq pads a
        width that is no multiple of cfg.ssd_chunk), a layer; for a
        model with K/V layers attn.chunk_calls, the call's chunk
        contractions (a K/V layer each), and attn.chunk_kernel, those of
        them that ran kernels/chunk_attention.py's kernel and wrote no
        score plane, by the call's own rule (tf.chunk_contractions)."""
        if _obs.active():
            _obs.counter("serving.prefill_tokens").add(tokens)
            _obs.counter("serving.prefill_rows").add(rows)
            self._count_frame_rows(rows)
            self._count_expert_matmuls(rows)
            if self._kv_layers:
                calls, kernel = tf.chunk_contractions(
                    self.params, self.cfg, self._lane_row, rows)
                _obs.counter("attn.chunk_calls").add(calls)
                _obs.counter("attn.chunk_kernel").add(kernel)
            if self._ssd_layers:
                _obs.counter("ssd.rows_live").add(
                    self._ssd_layers * tokens)
                _obs.counter("ssd.rows_scanned").add(
                    self._ssd_layers
                    * (rows + -rows % min(self.cfg.ssd_chunk, rows)))

    def _count_expert_matmuls(self, rows, passes=1):
        """While spans record, for a model with routed experts: the
        expert layers' grouped matmuls of `passes` passes of `rows`
        token rows each, into the counters moe.grouped_kernel (those
        that ran kernels/grouped_matmul.py's kernel) and
        moe.grouped_reference (those whose shapes kept
        jax.lax.ragged_dot): tf.expert_matmuls, the call's own rule."""
        if self.cfg.n_experts:
            kernel, reference = tf.expert_matmuls(
                self.params, self.cfg, rows)
            _obs.counter("moe.grouped_kernel").add(passes * kernel)
            _obs.counter("moe.grouped_reference").add(passes * reference)

    def _count_frame_rows(self, rows):
        """While spans record, for a model with hyper-connections
        (cfg.hc_mult): the token rows a dispatch passed through the
        n-stream frame, one a sub-layer (two a layer), into the counter
        hc.rows."""
        if self.cfg.hc_mult is not None:
            _obs.counter("hc.rows").add(2 * self.cfg.n_layers * rows)

    def _fresh_row(self, cfg=None):
        """A zeroed one-lane row of `cfg` (the target's, or the draft's)
        in one launch (_jitted_fresh_row): what an admission that found
        no cached prefix starts from."""
        return _jitted_fresh_row(self.cfg if cfg is None else cfg)()

    def _lookup_prefix(self, prompt):
        """Longest cached prefix of `prompt` -> (p_len, row_cache,
        last_row_logits-or-None). The cached trees are never mutated
        (prefill returns new arrays; the chunk-row wrapper does not
        donate), so one prefix serves any number of admissions."""
        best = None
        for key in self._prefix_cache:
            if len(key) <= len(prompt) \
                    and tuple(prompt[:len(key)]) == key:
                if best is None or len(key) > len(best):
                    best = key
        if best is None:
            return 0, self._fresh_row(), None
        hit = self._prefix_cache.pop(best)
        self._prefix_cache[best] = hit               # LRU refresh
        return len(best), hit[0], hit[1]

    def _paged_prefill(self, prompt, t_p, p_len, pfx_blocks,
                       pfx_logits):
        """Build the admission row cache through the pool: gather the
        cached prefix's blocks into a [1, max_len] row (zero-padded),
        prefill the suffix at bucket width (exactly the dense path's
        compile-once-per-bucket rule), and return (last_logits,
        row_cache). The shared blocks themselves are untouched — the
        row exists so the suffix's attention can read the prefix."""
        bs = self.block_size
        if p_len:
            nb_pfx = (p_len + bs - 1) // bs
            row_cache = _jitted_gather_row(self.cfg, nb_pfx)(
                self._pool,
                jnp.asarray(pfx_blocks[:nb_pfx], jnp.int32))
        else:
            row_cache = self._fresh_row()
        if p_len == t_p:
            return pfx_logits[0], row_cache
        logits, row_cache = self._prefill_rows(row_cache, prompt[p_len:],
                                               p_len)
        return logits[0], row_cache

    def _paged_map_lane(self, slot, t_p, row_cache, p_len, pfx_blocks,
                        lifetime, init_n):
        """Map a lane's block table for a fresh admission: the cached
        prefix's FULL blocks are shared in place (refcount++), the
        remainder through position t_p is freshly allocated and
        written whole-block from the row cache (copy-on-extend: a
        partial prefix tail is copied, never written shared), the rest
        of the lifetime is reserved for the lazy per-dispatch
        extension, and unneeded entries stay on the null block."""
        bs = self.block_size
        shared = p_len // bs
        own_n = init_n - shared        # >= 1: covers the first write
        own = self._alloc.alloc(own_n)
        if shared:
            self._alloc.share(pfx_blocks[:shared])
        self._alloc.reserve(lifetime - init_n)
        self._pool = _jitted_block_write(self.cfg, own_n)(
            self._pool, row_cache, jnp.asarray(own, jnp.int32),
            jnp.int32(shared * bs))
        lane = list(pfx_blocks[:shared]) + own
        trow = np.zeros((self._nb,), np.int32)
        trow[: len(lane)] = lane
        self._tables = _jitted_table_row(self.cfg)(
            self._tables, jnp.int32(slot), jnp.asarray(trow))
        self._lane_blocks[slot] = lane
        self._lane_need[slot] = lifetime

    def _ensure_coverage(self, k):
        """Allocate (lazily) the blocks the next k decode positions of
        every live lane will write, drawn from the reservation admit()
        made — the free list cannot run dry here, by accounting.
        Entries past a lane's lifetime need stay null: a request that
        finishes mid-chunk coasts its remaining writes into the
        garbage sink."""
        bs = self.block_size
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            end = min((int(self._pos[i]) + k - 1) // bs,
                      self._lane_need[i] - 1, self._nb - 1)
            while len(self._lane_blocks[i]) <= end:
                bid = self._alloc.alloc(1)[0]
                self._alloc.unreserve(1)
                j = len(self._lane_blocks[i])
                self._lane_blocks[i].append(bid)
                self._tables = _jitted_table_entry(self.cfg)(
                    self._tables, jnp.int32(i), jnp.int32(j),
                    jnp.int32(bid))

    def admit(self, prompt, n_new, seed=0, stop_token=None,
              enqueued_ns=None, priority=0, key=None):
        """Prefill `prompt` into a free slot; returns the request id,
        or None when every slot is busy. The first generated token is
        produced here (from the prefill logits), so a request with
        n_new=1 never occupies a decode lane. `seed` drives this
        request's sampling chain (ignored under greedy), exactly as
        generate(seed=...) would. `stop_token` ends the request early
        when emitted (EOS semantics; the stop token is included in the
        returned stream). `enqueued_ns` (perf_counter_ns) is when the
        request entered the caller's queue — with telemetry on it
        anchors the serving.queue_wait span and the serving.queue_ms /
        serving.ttft_ms histograms (run()/stream() pass it; without it
        TTFT is measured from this call). `priority` (larger = more
        important, default 0) drives KV-pressure PREEMPTION under
        paging: when the block pool cannot cover this admission, the
        lowest-priority strictly-below-`priority` lane is evicted to
        ``self.preempted`` (its synced prefix captured for a bit-exact
        resume via admit_continuation()) and its blocks fund this
        admission. With uniform priorities nothing is ever preempted.
        `key` is an optional IDEMPOTENCY key: a duplicate submission
        (same key, this batcher's dedup window) returns the ORIGINAL
        request's rid instead of double-admitting — still live, the
        caller keeps consuming its stream; already finished, the
        recorded result is re-delivered by the next step(). Dedup hits
        count ``serving.dedup_hits``; with a journal attached the
        window survives restarts (recover() repopulates it)."""
        # the whole admission: what lies beside prefill and patch (slot
        # search, block accounting, bookkeeping) is this span's self time
        sp = _obs.span("serving.admit", cat="serving")
        with sp:
            rid = self._admit_impl(prompt, n_new, seed, stop_token,
                                   enqueued_ns, priority, key)
            sp.args["rid"] = rid
            return rid

    def _admit_impl(self, prompt, n_new, seed, stop_token, enqueued_ns,
                    priority, key):
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if key is not None:
            hit = self._idem.get(key)
            if hit is None and key in self._idem_done:
                rid0, toks0 = self._idem_done[key]
                self._pending_finished[rid0] = list(toks0)
                hit = rid0
            if hit is not None:
                if _obs.enabled():
                    _obs.counter("serving.dedup_hits").add(1)
                    _obs.record_instant(
                        "serving.dedup", cat="serving",
                        args={"rid": hit, "key": str(key)})
                return hit
        # the sampling path below rebinds `key` to the PRNG chain —
        # keep the idempotency key under its own name past that point
        idem_key = key
        recording = self._ledger_gate()
        t0_ns = time.perf_counter_ns() if recording else None
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        t_p = len(prompt)
        if t_p < 1:
            raise ValueError("empty prompt")
        if t_p + n_new > self.cfg.max_len:
            raise ValueError("prompt+n_new %d exceeds max_len %d"
                             % (t_p + n_new, self.cfg.max_len))
        if self.brownout and self._bo_rung > 0 \
                and not self._brownout_admit_ok(priority):
            return None
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            return None
        if self.paged:
            # block-accounted admission: the prompt + n_new worst-case
            # demand (minus the cached prefix's shareable full blocks)
            # must fit the unpromised free list — LRU prefix eviction
            # may make room, a live lane's blocks never move
            if self._spec_on and self._spec_provider == "model":
                # the draft pool rides the TARGET's block tables, and
                # cached prefix blocks hold target K/V only — sharing
                # one would leave the draft cache blind over the
                # prefix, so model-draft paged serving prefills whole
                # (cache_prefix refuses; see there)
                p_len, pfx_blocks, pfx_logits = 0, [], None
            elif self.brownout and self._bo_rung >= 2:
                # brownout rung 2+: no NEW shared-prefix admissions —
                # sharing pins blocks past the sharer's own lifetime,
                # the opposite of what an exhausted pool needs
                p_len, pfx_blocks, pfx_logits = 0, [], None
            else:
                p_len, pfx_blocks, pfx_logits = \
                    self._lookup_prefix_blocks(prompt)
            shared = p_len // self.block_size
            lifetime, init_n = self._block_math(t_p, t_p + n_new)
            demand = lifetime - shared
            if demand > self.num_blocks - 1:
                raise ValueError(
                    "request needs %d KV blocks but the pool has only "
                    "%d usable (num_blocks=%d incl. the null block)"
                    % (demand, self.num_blocks - 1, self.num_blocks))
            if demand > self._alloc.available and not \
                    self._evict_prefixes(
                        demand,
                        keep=tuple(prompt[:p_len]) if p_len else None) \
                    and not self._preempt_for(demand, priority):
                return None
        rid = self._next_rid
        pre_span = _obs.span("serving.prefill", cat="serving", rid=rid,
                             lane=slot, prompt_tokens=t_p).start()
        if self.paged:
            last, row_cache = self._paged_prefill(
                prompt, t_p, p_len, pfx_blocks, pfx_logits)
            self._paged_map_lane(slot, t_p, row_cache, p_len,
                                 pfx_blocks, lifetime, init_n)
        else:
            # longest cached prefix (0 + a fresh row cache when none):
            # only the suffix prefills
            p_len, row_cache, pfx_logits = self._lookup_prefix(prompt)
            if p_len == t_p:
                last = pfx_logits[0]   # whole prompt is the prefix
            elif len(prefill_widths(self.cfg, t_p - p_len, p_len)) > 1:
                # a suffix wider than one call's chunk goes in through
                # _prefill_rows: whole chunks, then a bucketed rest
                logits, row_cache = self._prefill_rows(
                    row_cache, prompt[p_len:], p_len)
                last = logits[0]
            else:
                # one call, the code as it stood before _prefill_rows
                # (which computes the same): through the helper the
                # hybrid benchmark cell's warm set-up traced and lowered
                # its programs twice (+16 s; PERF.md section 7, not
                # explained), so this site keeps its own text.
                # clamp: the bucket can pass max_len (e.g. max_len=96,
                # suffix 70 -> bucket 128) and the cache axis is
                # max_len wide; width >= suffix always holds since
                # t_p + n_new <= max_len
                width = min(_bucket(t_p - p_len),
                            self.cfg.max_len - p_len)
                padded = np.zeros((1, width), np.int32)
                padded[0, : t_p - p_len] = prompt[p_len:]
                # one compiled prefill per bucket width (prefill_chunk
                # already specializes per chunk shape); fills positions
                # [p_len, p_len+width) — K/V rows beyond t_p are pad
                # garbage that decode overwrites before attention can
                # reach them. A recurrent state could not be healed
                # so: prefill_chunk stops it at the logits row, t_p - 1,
                # and it continues from the row cache's own (zeros, or
                # a cached prefix's state at p_len)
                logits, row_cache = \
                    tf._jitted_prefill_chunk_row(self.cfg)(
                        self.params, row_cache, jnp.asarray(padded),
                        jnp.int32(p_len), jnp.int32(t_p - p_len - 1))
                self._count_prefill(t_p - p_len, width)
                last = logits[0]
        # prefill-into-lane, all device-side: pick the first token
        # on device (generate()'s exact chain), patch the row
        # cache and the lane's (tok, pos, key) into the carry —
        # the patches consume the LAST dispatch's output buffers,
        # so they take effect at the next dispatch boundary while
        # the chunks already in flight keep reading their own
        # (older) buffers. The one host pull here is the first
        # token SCALAR, not the [vocab] logits row.
        first_dev, key = _jitted_admit_token(
            self.cfg, *self._controls)(last, jnp.int32(seed))
        with _obs.span("serving.patch", cat="serving", kind="admit",
                       lane=slot):
            if not self.paged:   # paged: blocks already scattered
                self._cache = _jitted_slot_write(self.cfg)(
                    self._cache, row_cache, jnp.int32(slot))
            self._dev_tok, self._dev_pos, self._dev_keys = \
                self._patch_fn(self._dev_tok, self._dev_pos,
                               self._dev_keys, jnp.int32(slot),
                               first_dev, jnp.int32(t_p), key)
        # the admission's launches are out: what is left is the host
        # waiting for the device
        with _obs.span("serving.first_token", cat="serving", rid=rid,
                       lane=slot):
            first = int(first_dev)
        self._pos[slot] = t_p          # next decode writes position t_p
        if self._spec_on:
            self._spec_admit(slot, prompt, t_p, first)
        pre_span.stop()
        req = Request(rid, prompt, n_new, stop_token, seed=seed,
                      priority=priority, key=idem_key)
        self._next_rid += 1
        req.tokens.append(first)
        req.emitted = 1
        self._slots[slot] = req
        self._round_admits += 1
        if idem_key is not None:
            self._idem[idem_key] = req.rid
        if self._journal is not None:
            # the submit record carries the first token (emitted=1):
            # replay resumes as a continuation from exactly here
            self._journal.append_submit(
                req.rid, req.tokens, n_new, seed=seed,
                stop_token=stop_token, priority=priority,
                key=idem_key, emitted=1)
        if recording:
            self._note_admit(req, slot, t0_ns, enqueued_ns)
        return req.rid

    def admit_continuation(self, tokens, n_more, seed=0, emitted=1,
                           stop_token=None, priority=0,
                           preempted_ns=None, resumes=None, key=None):
        """Resume a suspended stream BIT-exactly: `tokens` is the full
        synced history (prompt + `emitted` generated tokens), `n_more`
        the remaining budget. The cache is re-prefilled over
        tokens[:-1] and decode resumes feeding the last token at its
        true position — the requeue identity — and, under sampling,
        the per-request key chain is REPLAYED to its post-`emitted`
        state (split applied `emitted` times from PRNGKey(seed)), so a
        preempted-then-resumed stream is bit-identical to its
        uninterrupted solo run, sampled included (the dispatch-failure
        requeue path keeps its coarser reseed contract). Returns the
        NEW request id, or None when no lane/blocks are free.
        `preempted_ns` (perf_counter_ns of the preemption) feeds the
        serving.preempt_stall_ms histogram. `resumes` names the
        journaled rid this continuation supersedes (the park record's
        owner): with a journal attached the old rid is tombstoned
        (reason ``resume``) so a later replay resumes the NEW record
        only. `key` carries the original idempotency key forward."""
        if n_more < 1:
            raise ValueError("n_more must be >= 1")
        if emitted < 1:
            raise ValueError(
                "a continuation resumes a stream that emitted at "
                "least its first token (emitted >= 1)")
        recording = self._ledger_gate()
        t0_ns = time.perf_counter_ns() if recording else None
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        m = len(tokens) - 1
        if m < 1:
            raise ValueError("continuation needs prompt + first token")
        if len(tokens) + n_more > self.cfg.max_len:
            raise ValueError("history+n_more %d exceeds max_len %d"
                             % (len(tokens) + n_more, self.cfg.max_len))
        slot = next((i for i, s in enumerate(self._slots) if s is None),
                    None)
        if slot is None:
            return None
        if self.paged:
            lifetime, init_n = self._block_math(m, len(tokens) + n_more)
            if lifetime > self.num_blocks - 1:
                raise ValueError(
                    "continuation needs %d KV blocks but the pool has "
                    "only %d usable" % (lifetime, self.num_blocks - 1))
            if lifetime > self._alloc.available and not \
                    self._evict_prefixes(lifetime) \
                    and not self._preempt_for(lifetime, priority):
                return None
        rid = self._next_rid
        pre_span = _obs.span("serving.prefill", cat="serving", rid=rid,
                             lane=slot, kind="resume",
                             prompt_tokens=m).start()
        ctx, last = tokens[:-1], tokens[-1]
        _, row_cache = self._prefill_rows(self._fresh_row(), ctx, 0)
        key_np = self._resume_key(seed, emitted)
        if self.paged:
            self._paged_map_lane(slot, m, row_cache, 0, [], lifetime,
                                 init_n)
        else:
            self._cache = _jitted_slot_write(self.cfg)(
                self._cache, row_cache, jnp.int32(slot))
        with _obs.span("serving.patch", cat="serving",
                       kind="resume", lane=slot):
            self._dev_tok, self._dev_pos, self._dev_keys = \
                self._patch_fn(self._dev_tok, self._dev_pos,
                               self._dev_keys, jnp.int32(slot),
                               jnp.int32(last), jnp.int32(m),
                               jnp.asarray(key_np))
        self._pos[slot] = m
        if self._spec_on:
            self._spec_admit(slot, ctx, m, last)
        pre_span.stop()
        req = Request(rid, tokens, emitted + n_more, stop_token,
                      seed=seed, priority=priority, key=key)
        req.emitted = emitted
        self._next_rid += 1
        self._slots[slot] = req
        self._round_admits += 1
        if key is not None:
            self._idem[key] = req.rid
        if self._journal is not None:
            if resumes is not None:
                self._journal.append_finish(resumes, "resume")
            self._journal.append_submit(
                req.rid, req.tokens, req.n_new, seed=seed,
                stop_token=stop_token, priority=priority, key=key,
                emitted=emitted)
        if recording:
            t1 = self._stamp_admitted(req, t0_ns)
            req.t_admit_ns = t1
            if _obs.enabled():
                if preempted_ns is not None:
                    _obs.histogram("serving.preempt_stall_ms", "ms") \
                        .observe((t1 - preempted_ns) / 1e6)
                _obs.record_instant(
                    "serving.resumed", cat="serving",
                    args={"rid": rid, "lane": slot, "resume_pos": m,
                          "priority": priority})
                self._publish_occupancy()
        return rid

    def _resume_key(self, seed, emitted):
        """The per-request sampling key chain, replayed host-side to
        its state after `emitted` tokens: admit() splits PRNGKey(seed)
        once for the first token, every decode step splits once more
        and carries split()[0] — so the carried key after `emitted`
        tokens is split applied `emitted` times. This is what makes a
        preempted sampled stream resume bit-exactly (zeros under
        greedy: the chain is never read)."""
        if self.greedy:
            return np.zeros((2,), np.uint32)
        key = jax.random.PRNGKey(seed)
        for _ in range(int(emitted)):
            key = jax.random.split(key)[0]
        return np.asarray(key, np.uint32)

    def _preempt_for(self, demand, priority):
        """Fund a `priority` admission short `demand` available blocks
        by preempting strictly-lower-priority lanes, lowest priority
        first and the YOUNGEST (largest rid) within a class — the
        cheapest prefix to throw away. Victims are captured into
        ``self.preempted`` as (Request, preempt_ns) with their synced
        token prefix intact (in-flight emissions discard by rid at
        sync, the cancel() rule) and their blocks — speculative draft
        over-allocation included — return to the pool via _free().
        Returns True when the demand is covered. A cheap upper bound
        (every victim's whole lifetime need) guards against preempting
        work that could not cover the demand anyway; a shared prefix
        block that outlives its sharer can still leave the greedy loop
        short, in which case the victims resume later and the
        admission simply fails this round."""
        if not self.paged:
            return False
        victims = [i for i, r in enumerate(self._slots)
                   if r is not None and r.priority < priority]
        bound = self._alloc.available \
            + sum(self._lane_need[i] for i in victims)
        if bound < demand:
            return False
        while self._alloc.available < demand:
            live = [(r.priority, -r.rid, i)
                    for i, r in enumerate(self._slots)
                    if r is not None and r.priority < priority]
            if not live:
                break
            _, _, i = min(live)
            req = self._slots[i]
            t_ns = time.perf_counter_ns()
            if _obs.enabled():
                _obs.counter("serving.preemptions").add(1)
                _obs.record_instant(
                    "serving.preempt", cat="serving",
                    args={"rid": req.rid, "lane": i,
                          "priority": req.priority,
                          "for_priority": priority,
                          "synced": req.emitted})
            if self._journal is not None:
                self._journal.append_park(req.rid, req.tokens,
                                          req.emitted)
            avail0 = self._alloc.available
            self._free(i)
            if _obs.enabled():
                _events.event(
                    "preempt", rid=req.rid, lane=i,
                    victim_priority=req.priority,
                    for_priority=priority, synced=req.emitted,
                    blocks_freed=self._alloc.available - avail0)
            self.preempted.append((req, t_ns))
        return self._alloc.available >= demand

    # ---- elastic KV pool (memory pressure) ----

    def shrink_pool(self, n, preempt=True):
        """Give back ``n`` blocks of KV capacity under memory pressure
        (the OOM shrink-and-retry path and the ``kv_shrink`` brownout
        rung both land here). Escalation order, cheapest first:
        park free capacity beyond the admission promises -> evict
        unreferenced prefix-cache blocks -> park the lowest-priority
        lane through the PR 11 preemption path (it lands on
        ``self.preempted`` and resumes bit-exactly via
        ``admit_continuation``). ``preempt=False`` stops before that
        last step: the brownout controller runs inside ``step()``,
        where nobody drains ``self.preempted``, so a lane it parked
        would never finish — and when block exhaustion is what climbed
        the ladder, taking blocks from a live lane only deepens it.
        Returns the number of blocks actually parked (0 when not paged
        or nothing could be released)."""
        if not self.paged:
            return 0
        n = int(n)
        parked = self._alloc.shrink(n)
        while parked < n:
            need = n - parked
            self._evict_prefixes(need)     # best-effort; may be partial
            got = self._alloc.shrink(need)
            parked += got
            if got:
                continue
            if not preempt:
                break
            live = [(r.priority, -r.rid, i)
                    for i, r in enumerate(self._slots) if r is not None]
            if not live:
                break
            _, _, i = min(live)
            req = self._slots[i]
            t_ns = time.perf_counter_ns()
            if _obs.enabled():
                _obs.counter("serving.preemptions").add(1)
                _obs.record_instant(
                    "serving.preempt", cat="serving",
                    args={"rid": req.rid, "lane": i,
                          "priority": req.priority,
                          "reason": "kv_shrink",
                          "synced": req.emitted})
            if self._journal is not None:
                self._journal.append_park(req.rid, req.tokens,
                                          req.emitted)
            avail0 = self._alloc.available
            self._free(i)
            if _obs.enabled():
                _events.event(
                    "preempt", rid=req.rid, lane=i,
                    victim_priority=req.priority,
                    reason="kv_shrink", synced=req.emitted,
                    blocks_freed=self._alloc.available - avail0)
            self.preempted.append((req, t_ns))
        if parked and _obs.enabled():
            _obs.counter("serving.kv_shrinks").add(1)
            _obs.record_instant(
                "serving.kv_shrink", cat="serving",
                args={"requested": n, "parked": parked,
                      "pool_parked": self._alloc.parked_blocks})
            _events.event("pool", op="shrink", requested=n,
                          parked=parked,
                          pool_parked=self._alloc.parked_blocks)
        return parked

    def grow_pool(self, n):
        """Return ``n`` blocks of KV capacity: unpark shrink-ledger
        blocks first, then physically extend the device pool (zero
        blocks appended to every leaf — existing ids and tables stay
        valid) for the remainder. Physical growth preflights its byte
        cost against live headroom and fires the ``kv.pool.grow``
        chaos site, so a grow under pressure fails loudly instead of
        wedging the device. Returns the number of blocks returned to
        circulation."""
        if not self.paged or int(n) <= 0:
            return 0
        n = int(n)
        if _chaos.enabled():
            _chaos.fire("kv.pool.grow", blocks=n)
        got = self._alloc.grow(n)
        rest = n - got
        if rest > 0:
            nbytes = tf.paged_cache_nbytes(self.cfg, rest,
                                           self.block_size)
            if getattr(self, "_dpool", None) is not None:
                nbytes += tf.paged_cache_nbytes(self.draft_cfg, rest,
                                                self.block_size)
            if _membudget.enabled():
                _membudget.preflight_bytes(
                    "kv.pool.grow", nbytes,
                    signature="%d+%d" % (self.num_blocks, rest))
            self._pool = tf.grow_paged_cache(self._pool, rest)
            if getattr(self, "_dpool", None) is not None:
                self._dpool = tf.grow_paged_cache(self._dpool, rest)
            self._alloc.extend(rest)
            self.num_blocks += rest
            got += rest
        if _obs.enabled():
            _obs.record_instant(
                "serving.kv_grow", cat="serving",
                args={"requested": n, "returned": got,
                      "num_blocks": self.num_blocks})
            _events.event("pool", op="grow", requested=n,
                          returned=got, num_blocks=self.num_blocks)
        return got

    def _oom_shrink(self, exc):
        """A decode dispatch hit RESOURCE_EXHAUSTED: classify it
        through the membudget taxonomy and respond with
        shrink-and-retry — park part of the pool and let the next
        ``step()`` redispatch against the smaller footprint — instead
        of the PR 6 lane-rebuild (which would throw away every lane's
        device state for what is a capacity problem, not a corruption
        problem). An injected chaos OOM fires BEFORE the jitted chunk
        consumes its donated carry, so lane state is intact; a real
        post-donation OOM that persists after the shrink falls through
        to the rebuild on the next consecutive failure. Returns True
        when the shrink released capacity (the caller skips the
        rebuild)."""
        _membudget.note_oom(self._chaos_site, exc)
        parked = self.shrink_pool(self._kv_shrink_blocks())
        return parked > 0

    def _brownout_admit_ok(self, priority):
        """The rung-3/5 admission gates (rungs 1-2 act on the decode
        and prefix paths, rung 4 on the pool, not here): rung 3
        throttles to one admission per scheduling round, rung 5 sheds
        the lowest priority class outright."""
        if self._bo_rung >= 5 and priority <= 0:
            if _obs.enabled():
                _obs.counter("serving.brownout_rejections").add(1)
            return False
        if self._bo_rung >= 3 and self._round_admits >= 1:
            return False
        return True

    def _kv_shrink_blocks(self):
        """How many blocks the kv_shrink rung parks
        (MXNET_MEM_KV_SHRINK_BLOCKS; default a quarter of the usable
        pool)."""
        v = _fastenv.get("MXNET_MEM_KV_SHRINK_BLOCKS")
        try:
            n = int(v) if v else 0
        except (TypeError, ValueError):
            n = 0
        return n if n > 0 else max((self.num_blocks - 1) // 4, 1)

    def _brownout_tick(self):
        """One controller evaluation per scheduling round: sustained
        SLO-attainment drop (below `brownout_attain`), block
        exhaustion, or (membudget-armed) device headroom below the
        reserve climbs one rung after `brownout_trip` consecutive bad
        rounds; `brownout_clear` consecutive healthy rounds walk one
        rung back down. The asymmetric streaks are the hysteresis — a
        single good round under churn must not bounce the ladder."""
        self._round_admits = 0
        bad = False
        if _slo.active():
            att = _slo.attainment()
            if att is not None and att < self._brownout_attain:
                bad = True
        if self.paged and self._alloc.available <= 0:
            bad = True
        if not bad and self.paged and _membudget.enabled():
            # proactive kv_shrink driver: act on the headroom gauge
            # BEFORE the allocator notices anything (the gauge moves
            # first when a co-located training job or snapshot eats
            # the device)
            hb = _membudget.headroom_bytes()
            if hb is not None and hb < _membudget.reserve_bytes():
                bad = True
        if bad:
            self._bo_good = 0
            self._bo_bad += 1
            if self._bo_bad >= self._brownout_trip \
                    and self._bo_rung < 5:
                self._bo_bad = 0
                self._set_rung(self._bo_rung + 1)
        else:
            self._bo_bad = 0
            self._bo_good += 1
            if self._bo_good >= self._brownout_clear \
                    and self._bo_rung > 0:
                self._bo_good = 0
                self._set_rung(self._bo_rung - 1)

    def _set_rung(self, rung):
        prev = self._bo_rung
        self._bo_rung = rung
        if self.paged:
            # the kv_shrink rung (4) parks part of the pool on the way
            # up and returns it on the way down — the proactive twin of
            # the OOM shrink-and-retry path, minus its lane preemption
            # (admitted work is never stranded by the controller)
            if rung >= 4 and prev < 4 and not self._bo_parked:
                self._bo_parked = self.shrink_pool(
                    self._kv_shrink_blocks(), preempt=False)
            elif rung < 4 and prev >= 4 and self._bo_parked:
                try:
                    self.grow_pool(self._bo_parked)
                    self._bo_parked = 0
                except Exception as exc:
                    # a grow that OOMs (real or injected) leaves the
                    # pool shrunk — correctness never depends on
                    # growing back, only capacity does
                    if not _membudget.is_resource_exhausted(exc):
                        raise
                    _membudget.note_oom("kv.pool.grow", exc)
        if _obs.enabled():
            _obs.gauge("serving.brownout_rung").set(rung)
            _obs.record_instant("serving.brownout", cat="serving",
                                args={"rung": rung})
            _events.event("brownout", frm=prev, to=rung)

    def _kv_args(self):
        """The (cache, tables) pair every decode program takes: the
        block pool behind its tables, or the dense rows and None."""
        if self.paged:
            return self._pool, self._tables
        return self._cache, None

    def _register_dispatch(self, kind, fn, args):
        """Attribution over the serving jit boundary: register this
        dispatch executable (once per signature) so its named scopes —
        the paged_decode_kernel / paged_verify_kernel megakernel rows
        under MXNET_PAGED_DECODE_PALLAS=1 — appear in ops summaries
        and the obs_regression kernel baseline guard."""
        import jax as _jax
        leaves = [a for a in _jax.tree_util.tree_leaves(args)
                  if hasattr(a, "shape")]
        sig = _obs_recompile.signature_of(leaves)
        origin = "serving.%s.%s" % (kind, self.name or "batcher")
        if sig and _attr.needs_program(origin, sig):
            _attr.register_program(origin, sig, fn, args)

    # ---- decode ----

    def step(self):
        """One scheduling step over all slots, the batcher's ONE decode
        loop: top the in-flight window up to `pipeline_depth`
        dispatches (each issued against the previous dispatch's
        device-resident carry — no host sync between them), then sync
        ONLY the oldest one's emissions. A dispatch is `chunk_size`
        ragged decode steps in one device program (one for the default
        chunk_size=1) and appends up to chunk_size tokens to every
        active request; returns {rid: full token list} for the requests
        that finished this step (their slots are freed). A request
        hitting its stop token or budget mid-chunk ends there — the
        lane's remaining in-chunk tokens are discarded and its slot
        frees at the chunk boundary.

        At the default pipeline_depth=2 the host's share of a round runs
        beside the device's next one and tokens arrive one dispatch
        later (bounded staleness; see the class docstring); a window of
        one, pipeline_depth=1, returns a round's tokens from the step()
        that dispatched it.

        With spec_k set each dispatch is a speculative draft/verify
        round (up to chunk_size * (spec_k + 1) tokens per lane per
        dispatch) — per-lane emissions are ragged either way,
        speculation only makes the raggedness data-dependent — through
        the same window."""
        dispatch, sync = self._round
        # the whole round: what lies beside dispatch and sync (retire
        # loop, coverage, lane bookkeeping) is this span's self time
        with _obs.span("serving.step", cat="serving"):
            finished = {}
            if self._pending_finished:
                # re-delivery of deduped already-finished streams
                # (recover and idempotency hits) rides the next step's
                # return
                finished.update(self._pending_finished)
                self._pending_finished.clear()
            # retire requests already complete at admission (n_new=1,
            # or a stop token straight out of the prefill logits)
            for i, req in enumerate(self._slots):
                if req is not None and req.done:
                    finished[req.rid] = list(req.tokens)
                    if _obs.enabled():
                        self._note_finish(req)
                    self._note_done(req)
                    self._free(i)
            while (len(self._inflight) < self.pipeline_depth
                   and any(s is not None for s in self._slots)):
                try:
                    dispatch(self)
                except Exception as exc:  # noqa: BLE001 — requeue-or-raise
                    self._recover_dispatch_failure(exc)
                    self._end_round()
                    return finished
            if self._inflight:
                finished.update(sync(self))
            if not any(s is not None for s in self._slots):
                # nothing live: the remaining in-flight chunks only
                # advance parked lanes, so their emissions belong to no
                # request — drop the records (the device work itself is
                # already queued and harmless)
                self._inflight.clear()
            self._end_round()
            return finished

    def _count_dispatch(self, ahead, steps, window=1):
        """One more target-model dispatch, of `steps` passes through
        the layers with `window` token rows a lane each. While spans
        record, also the counters serving.dispatches
        and serving.dispatch_ahead: the dispatches issued while an older
        one was still unsynced, i.e. with the device already fed — every
        dispatch but the first after a drained window (a window of one
        is synced before the next dispatch, so it is never ahead);
        hc.rows (_count_frame_rows) for every lane; the
        expert layers' grouped matmuls (_count_expert_matmuls); the
        latent layers' stores of a step's `kr` rows (_count_row_stores);
        the K/V layers' decode contractions (_count_kv_contractions);
        and, for a model with Mamba-2 layers, ssd.lane_steps, the
        states a one-row dispatch read and wrote (every one of the
        max_batch lanes', a Mamba-2 layer a step), beside
        ssd.lane_steps_live, those of the lanes that held a request
        when it was issued."""
        self.dispatch_count += 1
        if _obs.active():
            _obs.counter("serving.dispatches").add(1)
            if ahead:
                _obs.counter("serving.dispatch_ahead").add(1)
            self._count_frame_rows(steps * window * self.max_batch)
            self._count_expert_matmuls(window * self.max_batch, steps)
            self._count_row_stores(steps)
            if window == 1:
                self._count_kv_contractions(steps)
                if self._ssd_layers:
                    each = steps * self._ssd_layers
                    _obs.counter("ssd.lane_steps").add(
                        each * self.max_batch)
                    _obs.counter("ssd.lane_steps_live").add(
                        each * self.active_count)

    @staticmethod
    def _count_routing(routing):
        """A dispatch's routing counts (a model with routed experts)
        into the counters moe.<name>."""
        for name, n in zip(tf.MOE_STATS, np.asarray(routing)):
            _obs.counter("moe." + name).add(int(n))

    def _count_row_stores(self, steps):
        """While spans record, for a model with latent attention: the
        latent layers' stores of the fresh `kr` rows of a dispatch of
        `steps` steps, into the counters mla.row_store_kernel (written
        in place by kernels/latent_decode.py latent_row_store) and
        mla.row_store_scatter (max_len rows that kernel cannot tile:
        the XLA scatter), by tf._dense_rows' own rule, latent_block."""
        if self._latent_layers:
            from ..kernels.latent_decode import latent_block
            tiled = latent_block(self.cfg.max_len) is not None
            _obs.counter("mla.row_store_kernel" if tiled else
                         "mla.row_store_scatter").add(
                steps * self._latent_layers)

    def _count_kv_contractions(self, steps):
        """While spans record, for a model with K/V layers: the decode
        contractions of a dispatch of `steps` one-row steps, a K/V layer
        a step, into the counters kv.decode_kernel (those that ran
        kernels/kv_decode.py's kernel: one pass over a lane's rows up to
        its position) and kv.decode_reference (a window layer's ring and
        the rows that kernel has no block for: the XLA text over the
        whole leaf), by the rows' shapes (tf.kv_decode_block, the call's
        own rule). The target model's contractions; a speculative round
        verifies through the chunk contraction and counts none."""
        if self._kv_layers:
            kernel = sum(block is not None for block in self._leaf_blocks)
            _obs.counter("kv.decode_kernel").add(steps * kernel)
            _obs.counter("kv.decode_reference").add(
                steps * (self._kv_layers - kernel))

    def _count_latent_rows(self, pos, live, steps):
        """A dispatch's latent rows (a model with latent attention)
        into the counters mla.rows_read, what its decode contractions
        fetch: for every lane, with a request or not, whole blocks up to
        the position the dispatch gave it (`pos`, all max_batch lanes at
        the first step, one more a step: kernels/latent_decode.py
        rows_fetched), a latent layer a step; and mla.rows_live, those
        of them at or before a live lane's own position: `live` holds
        each live lane's rows at the dispatch's first step (its tokens
        so far), one more a step. Its one caller, _sync_oldest, takes
        for live the lanes the chunk still speaks for at its sync (the
        request that owned the lane at dispatch still does and is not
        done), not every occupied slot."""
        from ..kernels.latent_decode import rows_fetched
        n = self._latent_layers
        _obs.counter("mla.rows_read").add(n * rows_fetched(
            np.asarray(pos)[None, :] + 1 + np.arange(steps)[:, None],
            self.cfg.max_len))
        _obs.counter("mla.rows_live").add(
            n * (steps * sum(live) + len(live) * steps * (steps - 1) // 2))

    def _count_kv_rows(self, pos, live, steps):
        """A dispatch's K/V rows (a model with window layers) into the
        counters kv.rows_read, what its decode contractions fetch, a
        layer a step, for every one of the max_batch lanes, with a
        request or not: where the contraction is kernels/kv_decode.py's
        kernel (self._leaf_blocks), whole blocks up to the position the
        dispatch gave a lane (`pos`, all max_batch lanes at the first
        step, one more a step: that file's rows_fetched); elsewhere the
        rows the leaf holds (max_len, or a window layer's ring: the XLA
        text contracts over the whole leaf and masks); kv.rows_ring, the
        part of them that lies in rings; and kv.rows_live, those a live
        lane's mask admits: `live` holds each live lane's positions at
        the dispatch's first step (its tokens so far), one more a step,
        and a ring admits no more than its rows. COMPUTED on the host
        from the leaves' shapes and the positions dispatched, as
        mla.rows_read is, not observed on the device. Called like
        _count_latent_rows."""
        from ..kernels.kv_decode import rows_fetched
        lengths = np.asarray(pos)[None, :] + 1 + np.arange(steps)[:, None]
        _obs.counter("kv.rows_read").add(sum(
            steps * self.max_batch * rows if block is None
            else rows_fetched(lengths, rows, block)
            for rows, block in zip(self._leaf_rows, self._leaf_blocks)))
        _obs.counter("kv.rows_ring").add(
            steps * self.max_batch * sum(rows for rows, _ in self._rings))
        _obs.counter("kv.rows_live").add(sum(
            min(n + j, rows) for n in live for j in range(steps)
            for rows in self._leaf_rows))

    def _end_round(self):
        """Per-scheduling-round epilogue shared by every step path:
        the brownout controller's tick, the MXNET_SERVING_DEBUG
        idle-point allocator audit, and the MXNET_MEM_GAUGE_EVERY
        device-memory gauge cadence. One guarded branch each when
        off."""
        if self.brownout:
            self._brownout_tick()
        if self._debug:
            self._debug_idle_check()
        if self._journal is not None:
            self._journal.maybe_gc()
        if _obs.enabled():
            if self._journal is not None:
                _obs.gauge("serving.journal_depth_bytes").set(
                    self._journal.depth_bytes)
                _obs.gauge("serving.journal_lag_records").set(
                    self._journal.lag_records)
            from .. import storage as _storage
            _storage.maybe_publish_device_memory_gauges()

    # ---- the plain dispatch / sync pair ----

    def _dispatch_chunk(self):
        """Issue one chunk against the device-resident carry and
        snapshot which request owned each lane at dispatch time — the
        identity that decides, at sync, whose stream each lane's
        emissions belong to (a lane re-admitted mid-flight discards
        the old occupant's in-flight tokens by rid mismatch)."""
        if self.paged:
            self._ensure_coverage(self.chunk_size)
        with _obs.span("serving.dispatch", cat="serving",
                       depth=len(self._inflight) + 1):
            if _chaos.enabled():
                _chaos.fire(self._chaos_site, mode="pipelined",
                            depth=len(self._inflight) + 1)
            args = (self.params,) + self._kv_args() + (
                self._dev_tok, self._dev_pos, self._dev_keys)
            if _membudget.enabled():
                _membudget.preflight(self._chaos_site, self._pipe_fn,
                                     args)
            if self.paged and _attr.ops_enabled():
                self._register_dispatch("pipeline", self._pipe_fn,
                                        args)
            toks, state, tables, tok, pos, keys, *routing = \
                self._pipe_fn(*args)
            if self.paged:
                self._pool, self._tables = state, tables
            else:
                self._cache = state
        self._dispatch_failures = 0
        self._count_dispatch(ahead=bool(self._inflight),
                             steps=self.chunk_size)
        self._dev_tok, self._dev_pos, self._dev_keys = tok, pos, keys
        self._inflight.append(
            (toks, [r.rid if r is not None else None
                    for r in self._slots],
             routing[0] if routing else None, self._pos.copy()))
        # every lane's device position advances k per chunk — mirror
        # it so the NEXT dispatch's coverage is exact
        self._pos += self.chunk_size
        if _obs.enabled():
            _obs.gauge("serving.inflight_depth").set(
                len(self._inflight))
            self._publish_occupancy()

    def _sync_oldest(self):
        """Fetch the oldest in-flight chunk's emissions and credit
        them to the requests that owned each lane when it was
        DISPATCHED (and still do): evicted or re-admitted lanes are
        discarded, a request ending mid-chunk keeps only its prefix.
        This is the only host-blocking point of step()."""
        toks_dev, lanes, routing, pos = self._inflight.popleft()
        counting = routing is not None and _obs.active()
        if counting:
            # a model with routed experts: the chunk's counts ride the
            # tokens' fetch, no round trip of their own
            routing.copy_to_host_async()
        with _obs.span("serving.sync", cat="serving",
                       behind=len(self._inflight)):
            toks = np.asarray(toks_dev).astype(np.int32)     # [k, B]
        if counting:
            self._count_routing(routing)
        if (self._latent_layers or self._rings) and _obs.active():
            # the lanes this chunk still speaks for (the loop below)
            live = [len(r.tokens) for r, rid in zip(self._slots, lanes)
                    if r is not None and r.rid == rid and not r.done]
            if self._latent_layers:
                self._count_latent_rows(pos, live, toks.shape[0])
            if self._rings:
                self._count_kv_rows(pos, live, toks.shape[0])
        recording = self._ledger_gate()
        obs_on = recording and _obs.enabled()
        t_sync = time.perf_counter_ns() if recording else None
        gaps = [0, 0, 0, 0] if recording else None
        finished = {}
        for i, rid in enumerate(lanes):
            if rid is None:
                continue
            req = self._slots[i]
            if req is None or req.rid != rid or req.done:
                continue               # canceled / replaced mid-flight
            grew = req.emitted
            for j in range(toks.shape[0]):
                req.tokens.append(int(toks[j, i]))
                req.emitted += 1
                if req.done:
                    break
            if self._journal is not None and req.emitted > grew:
                self._journal.append_emit(
                    req.rid, req.tokens[grew - req.emitted:],
                    req.emitted)
            if recording:
                self._note_progress(req, i, req.emitted - grew, t_sync,
                                    gaps)
            if req.done:
                finished[req.rid] = list(req.tokens)
                if obs_on:
                    self._note_finish(req, t_sync)
                self._note_done(req)
                self._free(i)
        if recording:
            self._count_gaps(gaps)
        if obs_on:
            self._publish_occupancy()
        return finished

    # ---- the speculative dispatch / sync pair (spec_k set) ----

    def _dispatch_spec(self):
        """Issue one speculative dispatch (chunk_size draft/verify
        rounds) against the device-resident carry. Paged coverage is
        reserved for the WORST case — every lane accepting every draft
        every round — and the sync reconciles `_pos` down to the
        measured acceptance, releasing the over-reserved draft blocks
        (see _reconcile_pos)."""
        worst = self.chunk_size * (self.spec_k + 1)
        if self.paged:
            self._ensure_coverage(worst)
        # brownout rung 1+: clamp the draft width to 1 — verify cost
        # collapses toward plain decode while the ladder is engaged,
        # and the adaptive controller takes back over on recovery
        keff_np = (np.minimum(self._keff, 1)
                   if self.brownout and self._bo_rung >= 1
                   else self._keff)
        keff = jnp.asarray(keff_np)
        with _obs.span("serving.dispatch", cat="serving", mode="spec",
                       depth=len(self._inflight) + 1,
                       spec_k=self.spec_k):
            if _chaos.enabled():
                _chaos.fire(self._chaos_site, mode="spec",
                            depth=len(self._inflight) + 1)
            if self._spec_provider == "ngram":
                if self.paged:
                    args = (self.params, self._pool, self._tables,
                            self._dev_hist, self._dev_tok,
                            self._dev_pos, keff)
                else:
                    args = (self.params, self._cache,
                            self._dev_hist, self._dev_tok,
                            self._dev_pos, keff)
            elif self.paged:
                args = (self.params, self.draft_params, self._pool,
                        self._dpool, self._tables, self._dev_tok,
                        self._dev_pos, keff)
            else:
                args = (self.params, self.draft_params, self._cache,
                        self._dcache, self._dev_tok, self._dev_pos,
                        keff)
            if _membudget.enabled():
                _membudget.preflight(self._chaos_site, self._spec_fn,
                                     args)
            if _attr.ops_enabled():
                self._register_dispatch("spec", self._spec_fn, args)
            if self._spec_provider == "ngram":
                if self.paged:
                    targets, emits, pool, hist, tok, pos = \
                        self._spec_fn(*args)
                    self._pool = pool
                else:
                    targets, emits, cache, hist, tok, pos = \
                        self._spec_fn(*args)
                    self._cache = cache
                self._dev_hist = hist
            elif self.paged:
                targets, emits, pool, dpool, tok, pos = \
                    self._spec_fn(*args)
                self._pool, self._dpool = pool, dpool
            else:
                targets, emits, cache, dcache, tok, pos = \
                    self._spec_fn(*args)
                self._cache, self._dcache = cache, dcache
        self._dispatch_failures = 0
        # every round verifies a window of spec_k + 1 rows a lane
        self._count_dispatch(ahead=bool(self._inflight),
                             steps=self.chunk_size, window=self.spec_k + 1)
        # worst-case position mirror so the NEXT dispatch's coverage
        # is sufficient whatever this one accepts; the sync subtracts
        # the measured shortfall back out
        self._pos += worst
        self._dev_tok, self._dev_pos = tok, pos
        self._inflight.append(
            (targets, emits,
             [r.rid if r is not None else None for r in self._slots],
             np.array(keff_np)))
        if _obs.enabled():
            _obs.gauge("serving.inflight_depth").set(
                len(self._inflight))
            self._publish_occupancy()

    def _sync_oldest_spec(self):
        """Fetch the oldest speculative dispatch's verified targets and
        emit counts, credit each lane's ACCEPTED tokens to the request
        that owned it at dispatch time (rid snapshot, exactly
        _sync_oldest's rule), feed the measured acceptance into the per-lane
        EWMA the adaptive-k controller reads, and reconcile paged
        block accounting down from worst case."""
        targets_dev, emits_dev, lanes, keffs = self._inflight.popleft()
        with _obs.span("serving.sync", cat="serving", mode="spec",
                       behind=len(self._inflight)):
            targets = np.asarray(targets_dev)      # [rounds, B, k+1]
            emits = np.asarray(emits_dev).astype(np.int64)  # [rounds, B]
        recording = self._ledger_gate()
        obs_on = recording and _obs.enabled()
        t_sync = time.perf_counter_ns() if recording else None
        gaps = [0, 0, 0, 0] if recording else None
        finished = {}
        rounds = emits.shape[0]
        for i, rid in enumerate(lanes):
            if rid is None:
                continue
            req = self._slots[i]
            if req is None or req.rid != rid or req.done:
                continue               # canceled / replaced mid-flight
            grew0 = req.emitted
            # keff at DISPATCH time: the width these rounds actually
            # drafted at, the denominator of their acceptance ratio
            keff_i = max(int(keffs[i]), 1)
            for r in range(rounds):
                e = int(emits[r, i])
                acc = e - 1            # accepted drafts this round
                self._spec_rounds += 1
                self._spec_drafted += keff_i
                self._spec_accepted += acc
                self._accept_ewma[i] += _SPEC_EWMA_ALPHA * (
                    acc / keff_i - self._accept_ewma[i])
                if obs_on:
                    _obs.histogram("serving.spec_accept_len",
                                   "tokens").observe(acc)
                for j in range(e):
                    req.tokens.append(int(targets[r, i, j]))
                    req.emitted += 1
                    if req.done:
                        break
                if req.done:
                    break
            if self._journal is not None and req.emitted > grew0:
                self._journal.append_emit(
                    req.rid, req.tokens[grew0 - req.emitted:],
                    req.emitted)
            if self.spec_accept_floor > 0.0:
                # per-lane adaptive k: measured acceptance under the
                # floor shrinks the draft width (never below 1 — one
                # draft still doubles the best-case tokens/dispatch),
                # at-or-above grows it back toward spec_k
                k0 = int(self._keff[i])
                if self._accept_ewma[i] < self.spec_accept_floor:
                    self._keff[i] = max(1, k0 - 1)
                else:
                    self._keff[i] = min(self.spec_k, k0 + 1)
                if int(self._keff[i]) != k0 and _obs.enabled():
                    _events.event(
                        "spec_k", lane=i, frm=k0,
                        to=int(self._keff[i]),
                        accept=round(float(self._accept_ewma[i]), 4))
            if recording:
                self._note_progress(req, i, req.emitted - grew0,
                                    t_sync, gaps)
            if req.done:
                finished[req.rid] = list(req.tokens)
                if obs_on:
                    self._note_finish(req, t_sync)
                self._note_done(req)
                self._free(i)
        self._reconcile_pos(emits, lanes)
        if recording:
            self._count_gaps(gaps)
        if obs_on:
            _obs.gauge("serving.spec_draft_ratio").set(
                self._spec_accepted / max(self._spec_drafted, 1))
            self._publish_occupancy()
        return finished

    def _reconcile_pos(self, emits, lanes):
        """Walk `_pos` back from the dispatch-time worst case to
        the measured per-lane advance and release the block tail the
        lane over-reserved for drafts it did not accept. Only lanes
        whose occupant is UNCHANGED since dispatch (rid snapshot
        matches) reconcile — a freed or re-admitted lane's patch
        already reset its accounting authoritatively."""
        worst = self.chunk_size * (self.spec_k + 1)
        advance = emits.sum(axis=0)
        for i, rid in enumerate(lanes):
            if rid is None:
                continue
            req = self._slots[i]
            if req is None or req.rid != rid:
                continue
            self._pos[i] -= worst - int(advance[i])
            if self.paged:
                self._trim_lane_blocks(i)

    def _trim_lane_blocks(self, i):
        """Release lane i's allocated blocks beyond its reconciled
        coverage, converting them back into reservation (the lane's
        lifetime need is unchanged — the blocks were just materialized
        early for a worst case that did not happen). Safe against
        in-flight dispatches: their writes are bounded by the KEPT
        coverage (every dispatch's worst case beyond the synced one is
        still counted in _pos), and a trimmed block's positions
        sit above every in-flight query position, so stale table
        snapshots can only reach it through masked-out attention rows.
        Trimmed blocks are always refcount-1: sharing only ever covers
        prompt-prefix blocks, which reconciled coverage never drops."""
        bs = self.block_size
        keep = min(max(int(self._pos[i]) - 1, 0) // bs,
                   self._lane_need[i] - 1) + 1
        blocks = self._lane_blocks[i]
        while len(blocks) > max(keep, 1):
            bid = blocks.pop()
            self._tables = _jitted_table_entry(self.cfg)(
                self._tables, jnp.int32(i), jnp.int32(len(blocks)),
                jnp.int32(0))
            self._alloc.release([bid])
            self._alloc.reserve(1)

    def _spec_admit(self, slot, ctx, t_p, first):
        """Seed lane `slot`'s draft state for a stream whose cache-
        resident prefix is the `t_p` tokens `ctx`, with `first` the
        lane's current token at position t_p. The n-gram provider gets
        its stream-history row (prefix + current token); the model
        provider gets a full draft-model prefill over the prefix, so
        draft steps and target verifies walk positions in lockstep
        (and, under paging, the same block tables)."""
        if self._spec_provider == "ngram":
            row = np.zeros((self.cfg.max_len,), np.int32)
            row[:t_p] = ctx
            row[t_p] = first           # t_p < max_len: n_new >= 1
            with _obs.span("serving.patch", cat="serving",
                           kind="spec_hist", lane=slot):
                self._dev_hist = self._hist_fn(
                    self._dev_hist, jnp.int32(slot), jnp.asarray(row))
            return
        drow = self._fresh_row(self.draft_cfg)
        width = min(_bucket(t_p), self.draft_cfg.max_len)
        padded = np.zeros((1, width), np.int32)
        padded[0, :t_p] = ctx
        with _obs.span("serving.prefill", cat="serving", kind="draft",
                       lane=slot, prompt_tokens=t_p):
            _, drow = tf._jitted_prefill_chunk_row(self.draft_cfg)(
                self.draft_params, drow, jnp.asarray(padded),
                jnp.int32(0), jnp.int32(t_p - 1))
            if self.paged:
                # the lane's freshly mapped blocks (all of them —
                # model-draft paging never shares a prefix, see
                # admit()) receive the draft rows whole-block
                own = self._lane_blocks[slot]
                self._dpool = _jitted_block_write(
                    self.draft_cfg, len(own))(
                        self._dpool, drow,
                        jnp.asarray(own, jnp.int32), jnp.int32(0))
            else:
                self._dcache = _jitted_slot_write(self.draft_cfg)(
                    self._dcache, drow, jnp.int32(slot))

    # ---- dispatch-failure recovery ----

    def _recover_dispatch_failure(self, exc):
        """A decode dispatch raised (injected fault, transient XLA
        failure). The jitted chunk donates its carry, so whatever it
        consumed is gone — rebuild the pool from scratch and REQUEUE
        every live request from its synced token state: lanes freed,
        carry re-zeroed, each request re-prefilled at its current
        prefix. Greedy streams continue bit-exactly (decode is a pure
        function of the token prefix); sampled streams continue on a
        deterministically reseeded chain (the in-flight key chain died
        with the carry). After ``_max_dispatch_failures`` consecutive
        failures the error re-raises — a deterministic fault must not
        loop as an infinite requeue."""
        self._dispatch_failures += 1
        if _obs.enabled():
            _obs.counter("serving.dispatch_failures").add(1)
            _obs.record_instant(
                "serving.dispatch_failed", cat="serving",
                args={"error": "%s: %s" % (type(exc).__name__, exc),
                      "consecutive": self._dispatch_failures})
        if self._dispatch_failures > self._max_dispatch_failures:
            raise exc
        if self.paged and _membudget.is_resource_exhausted(exc) \
                and self._oom_shrink(exc):
            # memory pressure, not corruption: the pool shrank and the
            # lanes are intact — the next step() retries as-is
            return
        pending = [r for r in self._slots if r is not None]
        self._rebuild_state()
        for req in pending:
            self._readmit(req)

    def _rebuild_state(self):
        """Rebuild every piece of device + scheduling state from
        scratch: slots emptied, pool/cache re-initialized, carry
        re-zeroed, allocator and prefix cache reset. Shared by the
        dispatch-failure requeue path (which then re-admits the live
        requests) and reset_lanes() (which drops them)."""
        self._slots = [None] * self.max_batch
        if self.paged:
            # the donated pool died with the dispatch — and the prefix
            # cache's blocks lived in it, so those entries die too
            # (re-cache_prefix() after recovery to restore sharing)
            self._pool = tf.init_paged_cache(self.cfg, self.num_blocks,
                                             self.block_size)
            self._tables = jnp.zeros((self.max_batch, self._nb),
                                     jnp.int32)
            self._alloc = BlockAllocator(self.num_blocks)
            self._lane_blocks = [[] for _ in range(self.max_batch)]
            self._lane_need = [0] * self.max_batch
            self._prefix_cache.clear()
            # the fresh allocator parks nothing: the brownout ledger
            # must agree, or its walk-down would grow past the
            # original pool
            self._bo_parked = 0
        else:
            self._cache = tf.init_cache(self.cfg, self.max_batch)
        self._pos = np.zeros((self.max_batch,), np.int32)
        self._inflight.clear()
        self._dev_tok = jnp.zeros((self.max_batch,), jnp.int32)
        self._dev_pos = jnp.zeros((self.max_batch,), jnp.int32)
        self._dev_keys = jnp.zeros((self.max_batch, 2), jnp.uint32)
        if self._spec_on:
            # the donated draft state died with the failed dispatch;
            # re-admission re-seeds each live lane's slice of it
            self._keff[:] = self.spec_k
            self._accept_ewma[:] = 1.0
            if self._spec_provider == "ngram":
                self._dev_hist = jnp.zeros(
                    (self.max_batch, self.cfg.max_len), jnp.int32)
            elif self.paged:
                self._dpool = tf.init_paged_cache(
                    self.draft_cfg, self.num_blocks, self.block_size)
            else:
                self._dcache = tf.init_cache(self.draft_cfg,
                                             self.max_batch)

    def reset_lanes(self):
        """Abandon every live request and rebuild the batcher to its
        just-constructed state (fresh pool, empty slots, zeroed carry,
        cleared failure count). The circuit-breaker revival path uses
        this to give a replica whose dispatch state may be poisoned a
        clean slate before routing its HALF-OPEN canary — the dead
        replica's requests were already drained to the router, so
        nothing live is lost. Raises whatever the device raises if the
        rebuild itself fails (the replica stays broken)."""
        self._rebuild_state()
        self._dispatch_failures = 0
        self.preempted = []
        self._bo_rung = self._bo_bad = self._bo_good = 0
        self._bo_parked = 0     # the rebuilt allocator parks nothing
        self._round_admits = 0
        if _obs.enabled():
            _obs.record_instant("serving.reset_lanes", cat="serving")

    def _readmit(self, req):
        """Put a live request back into a (guaranteed free) lane from
        its token history: the cache is re-prefilled over everything
        but the last token, and decode resumes feeding that last token
        at its true position — the standard continuation identity
        (cache holds keys for tokens[:-1], tok=tokens[-1],
        pos=len-1)."""
        slot = next(i for i, s in enumerate(self._slots) if s is None)
        ctx, last = req.tokens[:-1], req.tokens[-1]
        m = len(ctx)
        assert m >= 1, "a live request always has prompt + first token"
        _, row_cache = self._prefill_rows(self._fresh_row(), ctx, 0)
        if self.greedy:
            key_np = np.zeros((2,), np.uint32)
        else:
            key_np = np.asarray(jax.random.fold_in(
                jax.random.PRNGKey(req.seed), req.emitted), np.uint32)
        if self.paged:
            # remaining lifetime from the resume point (the fresh
            # allocator always fits what the old pool held — prefix
            # sharing died with it, but each request's own demand was
            # admission-checked without assuming sharing survives a
            # pool rebuild)
            total = len(req.tokens) + (req.n_new - req.emitted)
            lifetime, init_n = self._block_math(m, total)
            self._paged_map_lane(slot, m, row_cache, 0, [], lifetime,
                                 init_n)
        else:
            self._cache = _jitted_slot_write(self.cfg)(
                self._cache, row_cache, jnp.int32(slot))
        self._dev_tok, self._dev_pos, self._dev_keys = \
            self._patch_fn(self._dev_tok, self._dev_pos,
                           self._dev_keys, jnp.int32(slot),
                           jnp.int32(last), jnp.int32(m),
                           jnp.asarray(key_np))
        self._pos[slot] = m
        if self._spec_on:
            # re-seed the lane's draft state from the synced prefix —
            # the requeue resumes exactly like a fresh admission whose
            # prompt is everything synced so far
            self._spec_admit(slot, ctx, m, last)
        self._slots[slot] = req
        if _obs.enabled():
            _obs.record_instant("serving.requeued", cat="serving",
                                args={"rid": req.rid, "lane": slot,
                                      "resume_pos": m})
            # keep the request's flow chain alive across the requeue so
            # the trace ties pre-failure decode to the resumed lane
            _obs.record_flow("serving.request", req.rid, "t",
                             cat="serving",
                             args={"rid": req.rid, "lane": slot,
                                   "requeued": True})

    # ---- durability: crash recovery + weight hot-swap ----

    def recover(self):
        """Replay the attached journal after a process crash and
        re-enter every request it recorded.

        Finished requests (tombstone reason ``finish``, or a live
        record whose stream was already complete when the process
        died) are served from their recorded emissions — staged into
        the next step()'s return — and repopulate the idempotency
        window, so a client's re-submit dedups instead of recomputing.
        Live requests re-enter as continuations from their journaled
        synced prefix and resume BIT-exactly (greedy and sampled: the
        submit record carries the sampling seed and the synced count,
        and ``_resume_key`` replays the key chain). A live record that
        does not fit the current pool is parked on ``self.preempted``
        exactly like a PR 14 preemption victim — run()/the router
        resumes it when a lane frees.

        Returns ``(resumed, finished, skipped)``: old rid -> new rid
        (None = parked), rid -> final tokens, and the journal's
        skipped-record evidence (torn tail, CRC mismatch — each
        ``{"segment", "record", "reason"}``)."""
        if self._journal is None:
            raise RuntimeError(
                "recover() needs a journal attached "
                "(MXNET_SERVING_JOURNAL_DIR or journal=)")
        live, fin, skipped = self._journal.replay()
        # fresh-process rids must not collide with journaled ones: a
        # replayed fin for rid N must never tombstone a NEW request
        self._next_rid = max(self._next_rid,
                             self._journal.max_rid + 1)
        done = {}
        for rid, rec in fin.items():
            done[rid] = list(rec["tokens"])
            if rec.get("key") is not None:
                self._idem_done[rec["key"]] = (rid, list(rec["tokens"]))
        resumed = {}
        for rid in sorted(live):
            rec = live[rid]
            toks = list(rec["tokens"])
            emitted = int(rec["emitted"])
            n_more = int(rec["n_new"]) - emitted
            stop = rec["stop"]
            if emitted >= 1 and (n_more <= 0 or
                                 (stop is not None and toks
                                  and toks[-1] == stop)):
                # crashed after the final emission landed but before
                # the fin record did: the stream is complete — serve
                # it and write the tombstone now
                done[rid] = list(toks)
                if rec.get("key") is not None:
                    self._idem_done[rec["key"]] = (rid, list(toks))
                self._journal.append_finish(rid, "finish", tokens=toks)
                continue
            if emitted == 0:
                # never emitted (a router-side queue record): a fresh
                # admission replays the whole prompt
                new = self.admit(toks, rec["n_new"], seed=rec["seed"],
                                 stop_token=stop,
                                 priority=rec["prio"],
                                 key=rec.get("key"))
                if new is not None:
                    self._journal.append_finish(rid, "resume")
                resumed[rid] = new
                continue
            new = self.admit_continuation(
                toks, n_more, seed=rec["seed"], emitted=emitted,
                stop_token=stop, priority=rec["prio"],
                resumes=rid, key=rec.get("key"))
            if new is None:
                # capacity-blocked: park it like a preemption victim
                # (its journal record stays live, so a second crash
                # before it resumes still recovers it)
                req = Request(rid, toks, rec["n_new"], stop,
                              seed=rec["seed"],
                              priority=rec["prio"],
                              key=rec.get("key"))
                req.emitted = emitted
                self.preempted.append((req, time.perf_counter_ns()))
            resumed[rid] = new
        self._pending_finished.update(done)
        if _obs.enabled():
            _obs.counter("serving.journal_recoveries").add(1)
            _obs.record_instant(
                "serving.recover", cat="serving",
                args={"resumed": len(resumed), "finished": len(done),
                      "skipped": len(skipped)})
            _events.event("recover", resumed=len(resumed),
                          finished=len(done), skipped=len(skipped))
        return resumed, done, skipped

    def swap_weights(self, params, manifest=None):
        """Hot-swap the served weights without dropping a request.

        ``manifest`` gates the swap on PR 13's lineage machinery:
        a checkpoint-directory path runs ``verify_lineage`` (the
        newest retained manifest must verify) and reads its
        ``param_fingerprint``; a manifest dict supplies the
        fingerprint directly; None skips verification (rollback to an
        already-served params object). The incoming tree's recomputed
        fingerprint must MATCH — mismatched weights raise
        ``CheckpointCorrupt`` and the old params keep serving.

        HBM preflight (PR 14 membudget): old + new params are resident
        together during the swap; when that does not fit the budget the
        swap degrades to drain-then-swap (the old reference is dropped
        at the quiesce point before the new one is installed —
        ``mode="drain"`` in the result).

        The swap quiesces at a dispatch boundary: in-flight chunks are
        synced (their emissions deliver through the next step()), live
        lanes are captured, device state is rebuilt against the new
        params, and every live request re-enters through ``_readmit``
        — same continuation identity as the dispatch-failure requeue,
        so streams continue under the new weights with their synced
        prefixes intact. Returns ``{"fingerprint", "previous",
        "mode"}``."""
        from . import checkpoint as _ckpt
        want = None
        if isinstance(manifest, str):
            chain = _ckpt.verify_lineage(manifest)
            if not chain or chain[0]["status"] != "verified":
                raise _ckpt.CheckpointCorrupt(
                    "swap_weights: lineage of %s does not verify (%s)"
                    % (manifest,
                       chain[0]["status"] if chain else "no manifests"))
            with open(os.path.join(manifest, chain[0]["name"])) as f:
                want = json.load(f).get("param_fingerprint")
        elif isinstance(manifest, dict):
            want = manifest.get("param_fingerprint")
        new_fp = _integrity.params_fingerprint(params)
        if want is not None and new_fp != want:
            raise _ckpt.CheckpointCorrupt(
                "swap_weights: incoming parameter fingerprint %s does "
                "not match manifest %s — refusing unverified weights"
                % (new_fp, want))
        if _chaos.enabled():
            _chaos.fire("serving.swap", fingerprint=new_fp)
        mode = "resident"
        if _membudget.enabled():
            try:
                ok = _membudget.preflight_bytes(
                    "serving.swap", _membudget.tree_nbytes(params),
                    signature=new_fp)
            except _membudget.MemoryBudgetExceeded:
                ok = False
            if not ok:
                mode = "drain"
        prev_fp = self.weight_fingerprint
        # quiesce: sync every in-flight dispatch so no chunk computed
        # under the old weights lands after the swap (its emissions
        # deliver through _pending_finished at the next step())
        _, sync = self._round
        while self._inflight:
            self._pending_finished.update(sync(self))
        pending = [r for r in self._slots if r is not None]
        if mode == "drain":
            # drop the old reference before materializing against the
            # new one — the degraded path for budgets that cannot hold
            # both trees resident
            self.params = None
        self.params = params
        self._weight_fp = None
        # the cache/pool holds K/V computed under the OLD weights, and
        # so does a cached prefix's row (a paged prefix's blocks go with
        # the pool): rebuild from scratch and re-prefill every live
        # request under the new ones (same path as the dispatch-failure
        # requeue)
        self._rebuild_state()
        self._prefix_cache.clear()
        for req in pending:
            self._readmit(req)
        new_fp = self.weight_fingerprint
        if _obs.enabled():
            _obs.counter("serving.weight_swaps").add(1)
            _obs.record_instant(
                "serving.swap", cat="serving",
                args={"fingerprint": new_fp, "previous": prev_fp,
                      "mode": mode, "live": len(pending)})
            _events.event("swap", fingerprint=new_fp,
                          previous=prev_fp, mode=mode,
                          live=len(pending))
        return {"fingerprint": new_fp, "previous": prev_fp,
                "mode": mode}

    def cancel(self, rid):
        """Evict a request mid-decode (client disconnect, timeout):
        frees its slot immediately for the next admission. Returns the
        tokens emitted so far, or None when `rid` is not active (never
        admitted, finished, or already canceled). The other lanes'
        streams are untouched — eviction only parks the slot. Under
        pipelining "so far" means synced so far: tokens the lane
        emitted in still-in-flight chunks are discarded at their sync
        (rid mismatch), like any mid-flight identity change."""
        for i, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                out = list(req.tokens)
                if _obs.enabled():
                    self._note_finish(req, evicted=True)
                self._note_done(req, reason="cancel")
                self._free(i)
                return out
        return None

    def _free(self, i):
        """Free slot i. Idle lanes keep decoding (static batch shape);
        parking them at position 0 means their garbage K/V lands where
        the next admission's prefill overwrites it — defense in depth
        on top of the `attention <= pos` self-healing argument. The
        park is a device-side lane patch sequenced
        after the in-flight chunks (whose writes to this lane are the
        already-harmless idle-lane garbage)."""
        self._slots[i] = None
        if self.paged:
            # return the lane's references (a shared prefix block
            # frees only when its LAST sharer lets go) and the unused
            # tail of its reservation, then park the table on the
            # null block — in-flight chunks still write through their
            # dispatch-time tables, whole-block overwrites on
            # reallocation make that harmless
            blocks = self._lane_blocks[i]
            self._alloc.release(blocks)
            self._alloc.unreserve(self._lane_need[i] - len(blocks))
            self._lane_blocks[i] = []
            self._lane_need[i] = 0
            self._tables = _jitted_table_row(self.cfg)(
                self._tables, jnp.int32(i),
                jnp.zeros((self._nb,), jnp.int32))
        with _obs.span("serving.patch", cat="serving", kind="park",
                       lane=i):
            self._dev_tok, self._dev_pos, self._dev_keys = \
                self._patch_fn(self._dev_tok, self._dev_pos,
                               self._dev_keys, jnp.int32(i),
                               jnp.int32(0), jnp.int32(0),
                               jnp.zeros((2,), jnp.uint32))
        self._pos[i] = 0
        if self._spec_on:
            # reset the adaptive-k controller for the next occupant
            # (the hist row / draft cache need no clearing — the next
            # admission's _spec_admit overwrites them whole)
            self._keff[i] = self.spec_k
            self._accept_ewma[i] = 1.0

    # ---- request-level observability ----
    # Every caller guards on _ledger_gate() (= _obs.active()): with
    # neither gate open none of these run, no request is stamped and the
    # batcher pays exactly the guarded branches. Under a profiler session
    # alone _note_admit and _note_progress keep the request's stamps and
    # the gap ledger's four counters; everything that writes a histogram,
    # the ring or a gauge, _note_finish whole, stays behind
    # _obs.enabled() (docs/OBSERVABILITY.md "Request lifecycle").

    def _ledger_gate(self):
        """Do the requests' stamps record (`_obs.active()`)? Where the
        gate opens with requests in flight, their stamps are from before
        it closed or were never taken: each is unstamped, stamped anew
        at its first delivery, and that delivery is not counted."""
        on = _obs.active()
        if on != self._ledger_on:
            self._ledger_on = on
            if on:
                for req in self._slots:
                    if req is not None:
                        req.t_last_ns = None
        return on

    def _stamp(self, req, t_ns):
        """`req`'s newest token became host-visible at `t_ns`, with the
        admission ledger as it stands."""
        req.t_last_ns = t_ns
        req.admit_clock = self._admit_clock_ns
        req.admit_seq = self._admit_seq

    def _stamp_admitted(self, req, t_admit_ns):
        """The admitting call that began at `t_admit_ns` returns: the
        admission ledger moves by its duration BEFORE the new request is
        stamped, so a request never waits behind itself. -> now."""
        t1 = time.perf_counter_ns()
        self._admit_clock_ns += t1 - t_admit_ns
        self._admit_seq += 1
        req.t_first_ns = t1
        self._stamp(req, t1)
        return t1

    def _note_admit(self, req, lane, t_admit_ns, enqueued_ns):
        """Admission bookkeeping: the request's stamps and the admission
        ledger; under telemetry also the queue-wait span + histogram,
        the TTFT histogram, and the flow-chain start."""
        t1 = self._stamp_admitted(req, t_admit_ns)
        req.t_enq_ns = enqueued_ns
        req.t_admit_ns = t_admit_ns
        if not _obs.enabled():
            return
        if self._t_serve_start_ns is None:
            self._t_serve_start_ns = t_admit_ns
        if enqueued_ns is not None:
            q_ms = (t_admit_ns - enqueued_ns) / 1e6
            _obs.record_span("serving.queue_wait", "serving",
                             enqueued_ns, t_admit_ns,
                             {"rid": req.rid})
            _obs.histogram("serving.queue_ms", "ms").observe(q_ms)
            if _slo.check("queue_ms", q_ms):
                req.slo_bad = True
        # TTFT from enqueue when known (client-visible), else from the
        # admit call; the first token is produced inside admit()
        ttft_ms = (t1 - (enqueued_ns if enqueued_ns is not None
                         else t_admit_ns)) / 1e6
        _obs.histogram("serving.ttft_ms", "ms").observe(ttft_ms)
        if _slo.check("ttft_ms", ttft_ms):
            req.slo_bad = True
        _obs.record_flow("serving.request", req.rid, "s",
                         cat="serving",
                         args={"rid": req.rid, "lane": lane})
        self._publish_occupancy()

    def _note_progress(self, req, lane, grew, t_ns, gaps):
        """`grew` tokens of `req` became host-visible at `t_ns` (one
        chunk sync). The gap ledger, into this sync's sums `gaps` =
        [gaps, gap ns, gaps behind an admission, admission ns]: a chunk
        of k tokens is one gap of the chunk's time and k-1 of zero (the
        rule of a client's own count), behind an admission if one
        returned since the request's last stamp, by that many ns of
        admitting calls. Under telemetry also the inter-token-latency
        samples — the gap spread evenly over the chunk — plus the flow
        step tying this sync into the request's chain."""
        if grew <= 0:
            return
        t_last = req.t_last_ns
        if t_last is not None:
            gaps[0] += grew
            gaps[1] += t_ns - t_last
            if req.admit_seq != self._admit_seq:
                gaps[2] += 1
                gaps[3] += self._admit_clock_ns - req.admit_clock
        self._stamp(req, t_ns)
        if not _obs.enabled():
            return
        h = _obs.histogram("serving.itl_ms", "ms")
        gap_ms = ((t_ns - t_last) / 1e6 / grew
                  if t_last is not None else 0.0)
        for _ in range(grew):
            h.observe(gap_ms)
            if _slo.check("itl_ms", gap_ms):
                req.slo_bad = True
        _obs.record_flow("serving.request", req.rid, "t",
                         cat="serving",
                         args={"rid": req.rid, "lane": lane,
                               "tokens": grew})

    @staticmethod
    def _count_gaps(gaps):
        """One sync's sums into the four counters serving.gaps,
        serving.gap_ns, serving.gaps_behind_admit, serving.gap_admit_ns:
        made together at the first counted delivery, so a window without
        an admission reads 0 behind one and not nothing."""
        if gaps[0]:
            for name, n in zip(_GAP_COUNTERS, gaps):
                _obs.counter(name).add(n)

    def _note_finish(self, req, t_ns=None, evicted=False):
        """Request left the pool (finished or evicted): e2e histogram,
        goodput gauge, the flow-chain finish, a finish/evict instant,
        and the request's SLO verdict into the rolling attainment.
        Nothing of it is a profiler session's: its callers guard on
        _obs.enabled()."""
        t_ns = time.perf_counter_ns() if t_ns is None else t_ns
        start = req.t_enq_ns if req.t_enq_ns is not None \
            else req.t_admit_ns
        if start is not None and not evicted:
            e2e_ms = (t_ns - start) / 1e6
            _obs.histogram("serving.e2e_ms", "ms").observe(e2e_ms)
            if _slo.check("e2e_ms", e2e_ms):
                req.slo_bad = True
        # evicted requests still delivered their synced tokens
        self._completed_tokens += req.emitted
        if self._t_serve_start_ns is not None:
            elapsed_s = (t_ns - self._t_serve_start_ns) / 1e9
            if elapsed_s > 0:
                _obs.gauge("serving.goodput_tok_s").set(
                    self._completed_tokens / elapsed_s)
        _obs.record_flow("serving.request", req.rid, "f",
                         cat="serving", args={"rid": req.rid})
        _obs.record_instant(
            "serving.evict" if evicted else "serving.finish",
            cat="serving",
            args={"rid": req.rid, "emitted": req.emitted})
        if _slo.active():
            _slo.request_complete(not req.slo_bad)

    def _note_done(self, req, reason="finish"):
        """Terminal bookkeeping every finish site runs UNCONDITIONALLY
        (unlike the _obs-gated _note_finish): releases the request's
        idempotency claim — promoting a normally-finished one into the
        dedup window so a duplicate submit re-delivers its tokens —
        and writes the journal tombstone that lets GC truncate its
        segment."""
        if req.key is not None:
            if self._idem.get(req.key) == req.rid:
                self._idem.pop(req.key, None)
            if reason == "finish":
                self._idem_done[req.key] = (req.rid, list(req.tokens))
        if self._journal is not None:
            self._journal.append_finish(
                req.rid, reason,
                tokens=req.tokens if reason == "finish" else None)

    def _publish_occupancy(self):
        """Lane and KV-cache utilization gauges — the per-replica load
        signal the ROADMAP-1 router reads off the scrape endpoint."""
        active = self.active_count
        _obs.gauge("serving.lane_occupancy").set(active)
        _obs.gauge("serving.lane_utilization").set(
            active / float(self.max_batch))
        ctx = sum(len(r.tokens) for r in self._slots if r is not None)
        _obs.gauge("serving.kv_utilization").set(
            ctx / float(self.max_batch * self.cfg.max_len))
        _obs.gauge("serving.state_bytes").set(
            active * self._lane_state_bytes)
        _obs.gauge("serving.kv_bytes").set(self._kv_bytes())
        if self.paged:
            usable = self.num_blocks - 1
            free = self._alloc.free_blocks
            _obs.gauge("serving.kv_free_blocks").set(free)
            _obs.gauge("serving.kv_block_utilization").set(
                (usable - free) / float(usable))

    def _admit_job(self, job, enqueued_ns=None):
        """(prompt, n_new[, seed[, stop_token[, priority]]]) -> rid
        or None."""
        return self.admit(job[0], job[1],
                          seed=job[2] if len(job) > 2 else 0,
                          stop_token=job[3] if len(job) > 3 else None,
                          enqueued_ns=enqueued_ns,
                          priority=job[4] if len(job) > 4 else 0)

    def run(self, requests):
        """Convenience driver: serve `requests` (an iterable of
        (prompt, n_new[, seed[, stop_token[, priority]]])) through the
        slot pool, admitting as capacity frees. Returns {rid: tokens}
        for all of them, plus the admission order as a list of rids.
        A request preempted by a higher-priority admission is resumed
        automatically once capacity frees; its tokens land under its
        ORIGINAL rid (the resume allocates a fresh internal rid, which
        run() aliases back). With telemetry on, every job is stamped
        as enqueued at entry so queue-wait and TTFT cover time spent
        waiting for a lane. stream() does not resume preemptions —
        streaming callers own their requeue policy (the router does)."""
        enq_ns = time.perf_counter_ns() if _obs.enabled() else None
        queue = list(requests)
        order, results = [], {}
        alias = {}                     # resumed rid -> original rid
        while queue or self.preempted or self.active_count:
            while queue and self.has_capacity:
                rid = self._admit_job(queue[0], enqueued_ns=enq_ns)
                if rid is None:
                    break
                order.append(rid)
                queue.pop(0)
            # resume preempted work AFTER new admissions so a victim
            # cannot re-grab the blocks its preemptor was owed
            while self.preempted and self.has_capacity:
                req, t_ns = self.preempted[0]
                rid = self.admit_continuation(
                    req.tokens, req.n_new - req.emitted, seed=req.seed,
                    emitted=req.emitted, stop_token=req.stop_token,
                    priority=req.priority, preempted_ns=t_ns,
                    resumes=req.rid, key=req.key)
                if rid is None:
                    if not self.active_count:
                        raise RuntimeError(
                            "preempted request %d cannot resume on an "
                            "idle batcher" % req.rid)
                    break              # wait for capacity
                self.preempted.pop(0)
                alias[rid] = alias.get(req.rid, req.rid)
            results.update(self.step())
        if alias:
            results = {alias.get(rid, rid): toks
                       for rid, toks in results.items()}
        return results, order

    def stream(self, requests):
        """Streaming driver: yields ``(rid, token, done)`` the moment
        each token is produced — the first token right at admission
        (it comes from the prefill logits), then one per decode step
        per active lane; ``done`` marks a request's final token. Same
        admission policy and token streams as run() (the per-request
        generated tokens, concatenated, are identical — tested), but a
        caller can forward tokens to clients with no per-request
        buffering. A request cancel()ed between yields gets one
        terminal ``(rid, None, True)`` event — token None, since
        eviction produces no new token — so consumers keying cleanup
        off ``done`` always see it."""
        enq_ns = time.perf_counter_ns() if _obs.enabled() else None
        queue = list(requests)
        live = {}                    # rid -> Request (for delta tracking)
        while queue or self.active_count:
            while queue and self.has_capacity:
                rid = self._admit_job(queue[0], enqueued_ns=enq_ns)
                if rid is None:
                    break
                queue.pop(0)
                req = next(r for r in self._slots
                           if r is not None and r.rid == rid)
                live[rid] = req
                yield rid, req.tokens[-1], req.done
            already = {rid: req.emitted for rid, req in live.items()}
            finished = self.step()
            for rid, req in list(live.items()):
                grew = req.emitted - already[rid]   # up to chunk_size
                for off in range(grew):
                    last = off == grew - 1
                    yield (rid, req.tokens[-grew + off],
                           last and rid in finished)
                if rid in finished:
                    del live[rid]
                elif req not in self._slots:
                    # cancel()ed between yields: slot already freed, so
                    # step() will never report it finished — emit the
                    # terminal event ourselves
                    yield rid, None, True
                    del live[rid]
