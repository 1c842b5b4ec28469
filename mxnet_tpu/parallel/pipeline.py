"""Pipeline parallelism — stage-major layer stacking + collective-permute
microbatch schedule over the 'pp' mesh axis.

Capability extension over the reference (SURVEY §2.3 "NOT PRESENT": MXNet
1.x has only DP + manual-placement MP). TPU-native design: the L layers
of a homogeneous stack are grouped into S = |pp| stages; each device
holds its stage's L/S layer parameters (leading dim sharded over pp).
Microbatches enter stage 0 one per tick; activations rotate to the next
stage with lax.ppermute, so after the S-1-tick fill bubble every device
computes every tick (the GPipe schedule on an ICI ring). Everything is
lax.scan + ppermute: differentiable, one compiled program, no host
round-trips.

The whole schedule runs inside one jax.shard_map that is *manual* over
pp (and optionally other axes the caller's layer_fn needs, e.g. 'sp' for
ring attention inside a stage); the remaining mesh axes stay auto, so
tp/ep sharding of the layer weights continues to be GSPMD's job.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ring_permute

__all__ = ["stack_stage_params", "spmd_pipeline"]


def stack_stage_params(layer_params, n_stages):
    """List of L per-layer pytrees -> one pytree with leading dims
    [S, L/S] (stage-major), ready to shard P('pp', ...)."""
    L = len(layer_params)
    if L % n_stages != 0:
        raise ValueError("n_layers (%d) must divide by n_stages (%d)"
                         % (L, n_stages))
    per = L // n_stages
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)
    return jax.tree.map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]), stacked)


def spmd_pipeline(layer_fn, stage_params, x, mesh, axis_name="pp",
                  num_microbatches=None, extra_manual_axes=(),
                  microbatch_spec=None):
    """Apply L stacked layers to x through an S-stage pipeline.

    layer_fn(p_layer, x_mb) -> x_mb applies ONE layer to one microbatch.
    stage_params: pytree with leading dims [S, L/S] (stack_stage_params).
    x: [B, ...] global batch; split into num_microbatches (default S)
    along dim 0.
    extra_manual_axes/microbatch_spec: extend the manual region (e.g.
    manual 'sp' with the sequence dim of the microbatch sharded) for
    layer bodies that issue their own collectives.

    Returns y: [B, ...] == layer_fn applied L times to each sample.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    M = int(num_microbatches or S)
    B = x.shape[0]
    if B % M != 0:
        raise ValueError("batch %d must divide by num_microbatches %d"
                         % (B, M))
    mb = x.reshape((M, B // M) + x.shape[1:])
    mb_spec = microbatch_spec if microbatch_spec is not None else P()

    def per_stage(params_stage, mb_local):
        # leaves arrive as [1, L/S, ...]: drop the sharded stage dim
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        stage = jax.lax.axis_index(axis_name)
        n_stages = jax.lax.psum(1, axis_name)

        def apply_stage(h):
            def one_layer(h, p_layer):
                return layer_fn(p_layer, h), None
            h, _ = jax.lax.scan(one_layer, h, params_stage)
            return h

        def _varying(a):
            # freshly-created accumulators must be marked device-varying
            # over pp so the scan carry type matches its outputs (same
            # trick as ring.py ring_attention)
            try:
                return jax.lax.pcast(a, (axis_name,), to="varying")
            except (AttributeError, TypeError, ValueError):
                return a

        state = _varying(jnp.zeros_like(mb_local[0]))
        outs = _varying(jnp.zeros_like(mb_local))

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t; others consume the rotated
            # activation from their left neighbour
            x_in = jnp.where(stage == 0,
                             jax.lax.dynamic_index_in_dim(
                                 mb_local, jnp.clip(t, 0, M - 1), 0,
                                 keepdims=False),
                             state)
            y = apply_stage(x_in)
            # the last stage finished microbatch t-(S-1) this tick
            oi = jnp.clip(t - (n_stages - 1), 0, M - 1)
            valid = jnp.logical_and(t >= n_stages - 1,
                                    stage == n_stages - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, oi, 0,
                                                keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, y, prev), oi, 0)
            state = ring_permute(y, axis_name, 1)
            return (state, outs), None

        n_ticks = M + S - 1
        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast around the
        # ring so the result is replicated over pp
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    manual = set((axis_name,) + tuple(extra_manual_axes))
    out = jax.shard_map(per_stage, mesh=mesh,
                        in_specs=(param_specs, mb_spec),
                        out_specs=mb_spec,
                        axis_names=manual)(stage_params, mb)
    return out.reshape(x.shape)
