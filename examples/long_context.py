"""Long-context sequence parallelism demo: ring attention over a mesh.

Capability extension beyond the reference (SURVEY §5 long-context:
absent in MXNet 1.x; flagged as an extension). A sequence longer than
any single device's memory budget is sharded over the `sp` mesh axis;
ring attention streams K/V blocks around the ring (ppermute) so every
query block attends to the full sequence with O(T/sp) resident K/V.

Runs on the virtual CPU mesh out of the box:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context.py --seq-len 4096
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--help" not in sys.argv and "-h" not in sys.argv:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-parallel degree (default: all devices)")
    ap.add_argument("--check", action="store_true",
                    help="verify against single-device attention")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.ring import ring_attention_sharded

    devs = jax.devices()
    sp = args.sp or len(devs)
    mesh = Mesh(np.array(devs[:sp]).reshape(sp), ("sp",))
    T, H, D = args.seq_len, args.heads, args.head_dim
    assert T % sp == 0

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.rand(1, T, H, D).astype(np.float32)) * 0.1
    k = jnp.asarray(rs.rand(1, T, H, D).astype(np.float32)) * 0.1
    v = jnp.asarray(rs.rand(1, T, H, D).astype(np.float32))
    shard = NamedSharding(mesh, P(None, "sp", None, None))
    q, k, v = (jax.device_put(x, shard) for x in (q, k, v))

    # on a real TPU the per-shard block compute streams through the
    # Pallas flash kernel (kernels/flash_attention.flash_carry_block);
    # off-TPU the jnp blockwise path keeps numerics identical
    use_flash = jax.default_backend() == "tpu"
    fn = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, axis_name="sp", causal=True,
        use_flash_kernel=use_flash))
    out = fn(q, k, v)
    out.block_until_ready()
    t0 = time.time()
    out = fn(q, k, v)
    out.block_until_ready()
    dt = time.time() - t0
    print("ring attention: seq=%d over sp=%d devices "
          "(%d tokens/device resident K/V), %.1f ms/step"
          % (T, sp, T // sp, dt * 1000))

    if args.check:
        def reference(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        ref = reference(np.asarray(q), np.asarray(k), np.asarray(v))
        err = float(jnp.max(jnp.abs(out - ref)))
        print("max |ring - dense| = %.2e" % err)
        assert err < 1e-4
        print("MATCHES dense attention")


if __name__ == "__main__":
    main()
