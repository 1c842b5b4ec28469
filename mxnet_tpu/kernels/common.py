"""Shared plumbing for the Pallas kernel families.

kernels/flash_attention.py (training flash + per-sequence decode) and
kernels/paged_decode.py (the batched-lane paged decode/verify kernel)
need the same four things: the masking value, the lane-padded stat
layout, the block clamp/divisibility rule, and a per-shape block_k
choice cache. They live here so neither family copies the other —
a fix to the mask or the block rule lands in both kernels at once.
"""

import math
import os
import warnings

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Row statistics (m, l, lse, delta, amax) ride through HBM/VMEM with a
# trailing lane dimension, every lane holding the same value. Mosaic
# requires the last two dims of any block to be (8, 128)-divisible or
# equal to the array dims; a [rows]-shaped stat with the batch dim
# squeezed out of the block violates that, so [rows, 128] is the
# lowerable layout (same choice as jax's reference TPU kernels). The
# rule's "equal to the array dim" clause also admits [rows, 1] blocks
# at 1/128th the stat HBM traffic — env-overridable for an on-chip A/B
# (not measured). The ring's carry, the decode kernels and the paged
# kernel use it; the training forward and backward of
# flash_attention.py keep their statistics as ROWS [1, rows] in HBM.
STAT_LANES = int(os.environ.get("MXNET_FLASH_STAT_LANES", "128"))

MIN_BLOCK = 8           # below this the grid is degenerate, not tiled


def causal_mask(s, q_start, k_start, block_q, block_k):
    """Mask score block s [block_q, block_k] to the causal triangle:
    global query row q_start+i may attend global key k_start+j only
    when q_pos >= k_pos."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def length_mask(s, k_start, limits):
    """Mask key positions at/past each row's valid length: s
    [rows, block_k] scores for global key positions starting at
    k_start; ``limits`` is a [rows, 1] (or scalar) EXCLUSIVE bound —
    row r attends k_pos < limits[r]. The decode kernels' dynamic-
    length mask (one compiled program serves every position)."""
    rows, block_k = s.shape
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (rows, block_k), 1)
    return jnp.where(k_pos < limits, s, NEG_INF)


def adjust_block(block, seq, name, family="flash_attention"):
    """Clamp ``block`` to ``seq`` and make it divide; refuse to let the
    gcd collapse toward 1 (prime/odd T with a non-dividing block) —
    that is a correct but pathologically fine grid of near-one-element
    steps. Fall back to ONE full-sequence block and warn so an explicit
    or env block choice that does not divide T is visible (ADVICE r5:
    previously a silent degenerate grid)."""
    adjusted = min(block, seq)
    if seq % adjusted:
        adjusted = math.gcd(seq, adjusted)
    if adjusted < min(seq, MIN_BLOCK):
        warnings.warn(
            "%s: %s=%d does not divide sequence length %d "
            "and the gcd adjustment collapses to %d (a degenerate "
            "%d-step grid); falling back to a single full-sequence "
            "block of %d. Pick a %s that divides the sequence to tile "
            "properly." % (family, name, block, seq, adjusted,
                           seq // max(adjusted, 1), seq, name),
            stacklevel=3)
        return seq
    return adjusted


# ------------------------------------------- per-shape block_k cache ---
# Both decode kernel families pick block_k the same way: largest
# preferred tile that divides the cache length (falling back to one
# full-length block). The choice is pure shape math, but it sat on the
# per-call path of flash_decode_with_lse (recomputed every call) and
# the paged kernel adds an env override + a pool-block multiple
# constraint — so the choice is computed once per distinct shape key
# and memoized process-wide. The cache is tiny (a handful of serving
# shapes per process) and never evicts.

_BLOCK_CHOICE = {}


def choose_block_k(t_max, shape_key=(), candidates=(512, 256, 128),
                   multiple=1, env=None):
    """The cached block_k for a cache of length ``t_max``.

    ``shape_key`` distinguishes callers/shapes that would otherwise
    collide (kernel family, batch, heads, head_dim, dtype...).
    ``candidates`` are tried in order; the first that divides t_max and
    is a multiple of ``multiple`` (the paged pool's block size — a
    grid step stages whole pool blocks) wins, else ONE full-length
    block. ``env`` names an env var holding an explicit override,
    validated against the same constraints (invalid values warn and
    fall back rather than building an untileable grid)."""
    key = (env, int(t_max), int(multiple)) + tuple(shape_key)
    hit = _BLOCK_CHOICE.get(key)
    if hit is not None:
        return hit
    choice = None
    if env:
        raw = os.environ.get(env)
        if raw:
            try:
                val = int(raw)
            except ValueError:
                val = -1
            if val > 0 and val % multiple == 0 and t_max % val == 0:
                choice = val
            else:
                warnings.warn(
                    "%s=%r is not a positive multiple of %d dividing "
                    "cache length %d; using the default block choice"
                    % (env, raw, multiple, t_max), stacklevel=2)
    if choice is None and env == "MXNET_PAGED_BLOCK_K" \
            and os.environ.get("MXNET_OBS_PROFILE_DIR"):
        # an ARCHIVED winner beats the static heuristic: the profile
        # store holds measured p50s per MXNET_PAGED_BLOCK_K config
        # fingerprint from past A/B runs (ISSUE 18 / ROADMAP item 5's
        # predict-and-prune). Only for callers keyed on that knob —
        # flash_decode doesn't honor it, so a paged winner must not
        # leak into its grid. One guarded branch — with the store
        # unset this is a single env read; the memo above means the
        # archive is consulted once per distinct shape key.
        try:
            from ..observability import costmodel
            choice = costmodel.archived_block_k(t_max,
                                                multiple=multiple)
        except Exception:
            choice = None
    if choice is None:
        choice = next((bb for bb in candidates
                       if bb % multiple == 0 and t_max % bb == 0),
                      t_max)
    choice = min(choice, t_max)
    _BLOCK_CHOICE[key] = choice
    return choice


def block_choice_cache():
    """Snapshot of the memoized choices (tests / diagnostics)."""
    return dict(_BLOCK_CHOICE)
