"""Autoregressive decode throughput: tokens/s through the KV-cache path.

Measures models/transformer.py decode_step (flash_decode kernel vs the
dense masked einsum) at growing cache lengths — decode is HBM-bound
(cache bytes read per token), so tokens/s should track 1/length.

    python - < benchmark/decode_bench.py                 # dense (default)
    MXNET_DECODE_FLASH=1 python - < benchmark/decode_bench.py   # Pallas leg

Run from the repo root via stdin so cwd lands on sys.path.
"""

import os
import time

import numpy as np

BATCH = int(os.environ.get("MXNET_DECODE_BATCH", "8"))
STEPS = int(os.environ.get("MXNET_DECODE_STEPS", "64"))
# default matches the shipped TransformerConfig default (dense decode
# attention); MXNET_DECODE_FLASH=1 opts in to the Pallas kernel leg
USE_FLASH = os.environ.get("MXNET_DECODE_FLASH", "0") not in ("0", "false")


def main():
    from benchmark.common import fetch_barrier
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf

    kvh = int(os.environ.get("MXNET_DECODE_KV_HEADS", "0"))
    shapes = ((1024, 512, 8, 8), (4096, 512, 8, 8))
    if os.environ.get("MXNET_DECODE_SMOKE"):   # CPU-sized correctness run
        shapes = ((64, 32, 2, 1),)
    for max_len, d_model, heads, layers in shapes:
        cfg = tf.TransformerConfig(
            vocab_size=32000, d_model=d_model, n_heads=heads,
            n_kv_heads=kvh or None,
            n_layers=layers, d_ff=4 * d_model, max_len=max_len,
            dtype=jnp.bfloat16, use_flash_kernel=USE_FLASH,
            kv_cache_int8=os.environ.get("MXNET_DECODE_KV_INT8", "0")
            .lower() not in ("0", "false", ""))
        params = tf.init_params(cfg, seed=0)
        cache = tf.init_cache(cfg, BATCH)
        step = tf.make_decode_step(cfg)
        tok = jnp.zeros((BATCH,), jnp.int32)
        # warm at the tail position (worst case: full cache read)
        logits, cache = step(params, cache, tok, max_len - STEPS - 1)
        fetch_barrier(logits)
        t0 = time.time()
        for i in range(STEPS):
            logits, cache = step(params, cache, tok,
                                 max_len - STEPS + i)
        fetch_barrier(logits)
        dt = time.time() - t0
        toks = BATCH * STEPS
        mode = ("int8kv" if cfg.kv_cache_int8
                else ("flash" if USE_FLASH else "dense"))
        print("decode %s%s max_len=%d bs=%d: %.1f tok/s (%.2f ms/step)"
              % (mode, (" kvh=%d" % kvh) if kvh else "", max_len,
                 BATCH, toks / dt, dt / STEPS * 1e3))


if __name__ == "__main__":
    main()
