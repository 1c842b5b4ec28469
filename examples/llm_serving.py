"""Autoregressive LLM serving: train a toy LM, then decode with the
KV-cache path — single-device or TP/DP-sharded over a mesh.

The inference-side counterpart of examples/transformer_lm.py: the same
SPMD transformer (models/transformer.py) serves token-by-token through
init_cache/decode_step/generate; on TPU the per-step attention streams
the cache through one fused XLA contraction (--flash opts in to the
Pallas decode kernel; the chip A/B measured dense ~5x faster at
serving shapes, docs/SERVING.md). The reference has no
decode/serving path (its transformer surface stops at the
interleaved-matmul ops, src/operator/contrib/transformer.cc) — this is
the capability extension the long-context stack implies.

    python examples/llm_serving.py                 # the devices jax finds
    python examples/llm_serving.py --no-mesh       # single device
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llm_serving.py             # 8-dev virtual CPU mesh
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=24)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--no-mesh", action="store_true")
    ap.add_argument("--flash", action="store_true",
                    help="decode through the Pallas flash kernel "
                         "(A/B lever; dense is the measured-faster "
                         "default)")
    ap.add_argument("--int8", action="store_true",
                    help="serve from weight-only int8 params "
                         "(quantize_weights_int8)")
    ap.add_argument("--beam", type=int, default=0,
                    help="also decode with beam search of this width")
    ap.add_argument("--paged-router", action="store_true",
                    help="also serve the prompts through a 2-replica "
                         "ReplicaRouter over paged-KV batchers "
                         "(docs/SERVING.md 'Paged KV cache' / "
                         "'Routing'); streams must equal generate()")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: use this many KV "
                         "heads (< heads shrinks the cache)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    vocab = 16
    cfg = T.TransformerConfig(
        vocab_size=vocab, d_model=48, n_heads=4, n_layers=2, d_ff=96,
        n_kv_heads=args.kv_heads or None,
        max_len=args.seq + args.gen, use_flash_kernel=args.flash,
        use_ring_attention=False)
    params = T.init_params(cfg, seed=0)
    mom = T.init_momentum(params)
    step = T.make_train_step(cfg, lr=0.1)

    rs = np.random.RandomState(0)
    # a fixed corpus of period-4 patterns: the model memorizes them, so
    # greedy decoding from any prefix must reproduce the continuation
    corpus = rs.randint(1, vocab, (args.batch, 4))

    def batch_tokens(seq):
        return np.tile(corpus, (1, seq // 4 + 1))[:, :seq].astype(
            np.int32)

    toks = jnp.asarray(batch_tokens(cfg.max_len))
    loss = None
    for i in range(args.steps):
        params, mom, loss = step(params, mom, toks)
    if loss is not None:
        print("trained: final loss %.4f" % float(loss))

    # serve: prompt with the first 5 tokens (one period + 1) of two
    # corpus sequences; greedy decode must continue each pattern
    prompt_np = batch_tokens(5)[:2]
    prompt = jnp.asarray(prompt_np)

    if args.int8:
        params = T.quantize_weights_int8(params)
    mesh = None
    if args.no_mesh:
        tag = "single-device"
    else:
        n = len(jax.devices())
        tp = 2 if n % 2 == 0 else 1
        dp = 2 if n % (2 * tp) == 0 else 1
        mesh = make_mesh({"dp": dp, "tp": tp,
                          "rest": n // (dp * tp)})
        params = T.shard_params(params, cfg, mesh)
        tag = "mesh dp=%d tp=%d" % (dp, tp)

    t0 = time.time()
    out = T.generate(params, prompt, args.gen, cfg, mesh=mesh)
    out = np.asarray(out)
    dt = time.time() - t0
    period = prompt_np[:, :4]
    expect = np.tile(period, (1, out.shape[1] // 4 + 1))[:, :out.shape[1]]
    match = (out == expect).mean()
    print("served %s%s: %d tokens in %.2fs, pattern match %.2f"
          % (tag, " int8-weights" if args.int8 else "", out.size, dt,
             match))
    print("sample:", out[0].tolist())
    if args.beam:
        seqs, scores = T.beam_search(params, prompt, args.gen, cfg,
                                     beam=args.beam, mesh=mesh)
        best = np.asarray(seqs)[:, 0]
        print("beam-%d best: %s (score %.3f)"
              % (args.beam, best[0].tolist(),
                 float(np.asarray(scores)[0, 0])))
        if not np.array_equal(best, expect):
            print("FAILED: beam search diverged from the learned "
                  "pattern")
            return 1
    if match < 0.95:
        print("FAILED: generation diverged from the learned pattern")
        return 1
    if args.paged_router:
        # the fleet path: 2 paged-KV replicas behind the SLO-aware
        # router; every stream must be bit-exact vs solo generate()
        from mxnet_tpu.models.router import ReplicaRouter
        bs = 4 if cfg.max_len % 4 == 0 else 1
        router = ReplicaRouter.build(params, cfg, n_replicas=2,
                                     max_batch=2, paged=True,
                                     block_size=bs)
        jobs = [(prompt_np[i].tolist(), args.gen)
                for i in range(prompt_np.shape[0])]
        results, order = router.run(jobs)
        for i, rid in enumerate(order):
            if results[rid] != out[i].tolist():
                print("FAILED: routed stream %d diverged from "
                      "generate()" % i)
                return 1
        print("paged router: %d requests over 2 replicas, streams "
              "bit-exact vs generate()" % len(jobs))
    print("SERVED OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
