"""Flash-attention kernel vs dense jnp attention on chip.

Run on the real TPU (no JAX_PLATFORMS override). At 8k-32k sequence the
dense path materialises the [T, T] score matrix (64M-1G floats per
batch*head) while the Pallas kernel streams K/V blocks through VMEM —
this measures both the speed and the feasibility boundary (dense OOMs
where flash keeps going).

Prints one JSON line per (seq, path): fwd ms, fwd+bwd ms, TFLOP/s.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.flash_attention import flash_attention

    B, H, D = 4, 8, 128
    causal = True

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        T = q.shape[1]
        # causal mask from iotas, NOT jnp.tril(ones((T,T))): the
        # materialized constant is T^2 bytes at COMPILE time (1 GB at
        # T=32768) and crashes the remote compile helper
        iq = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        s = jnp.where((iq >= ik)[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, v.astype(a.dtype)) \
            .astype(q.dtype)

    from benchmark.common import fetch_barrier as _sync

    def run(fn, q, k, v, steps=10):
        out = fn(q, k, v)
        _sync(out)
        t0 = time.time()
        for _ in range(steps):
            out = fn(q, k, v)
        _sync(out)
        return (time.time() - t0) / steps

    def run_grad(fn, q, k, v, steps=10):
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)))
        out = g(q, k, v)
        _sync(out)
        t0 = time.time()
        for _ in range(steps):
            out = g(q, k, v)
        _sync(out)
        return (time.time() - t0) / steps

    # 4096 exists so dense has a row that surely fits — the
    # flash-vs-dense crossover; above it dense is expected to die
    for T in (4096, 8192, 16384, 32768):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32),
                        jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32),
                        jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32),
                        jnp.bfloat16)
        # causal attention FLOPs: ~2 * 2 * B*H*T^2/2*D each for QK^T and
        # PV = 2*B*H*T^2*D total (fwd)
        flops = 2.0 * B * H * T * T * D

        legs = [("flash", lambda q, k, v: flash_attention(
            q, k, v, causal=causal))]
        # dense rows ignore the flash block/stat knobs, so A/B legs
        # (block256, stat_lanes1) skip them instead of re-burning
        # chip-window time on rows the baseline leg already measured
        if os.environ.get("MXNET_FLASH_BENCH_SKIP_DENSE",
                          "0").lower() in ("0", "false", ""):
            legs.append(("dense", jax.jit(dense)))
        for name, fn in legs:
            # fwd and fwd+bwd fail independently (dense fwd can fit
            # where its grad OOMs — exactly the feasibility boundary
            # this sweep maps), so each leg is caught separately and a
            # successful fwd measurement is never discarded
            row = {"metric": "attn_%s_T%d" % (name, T), "unit": "ms"}
            try:
                fwd = run(fn, q, k, v)
                row["fwd_ms"] = round(fwd * 1e3, 2)
                row["fwd_tflops"] = round(flops / fwd / 1e12, 2)
            except Exception as e:
                row["error"] = type(e).__name__
                row["detail"] = str(e)[:200]
                print(json.dumps(row))
                continue
            try:
                fb = run_grad(fn, q, k, v)
                row["fwd_bwd_ms"] = round(fb * 1e3, 2)
            except Exception as e:
                row["bwd_error"] = type(e).__name__
                row["bwd_detail"] = str(e)[:200]
            print(json.dumps(row))


if __name__ == "__main__":
    main()
