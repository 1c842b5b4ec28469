"""Train the flagship SPMD transformer LM on a toy language.

The user-facing counterpart of __graft_entry__.dryrun_multichip: the
same dp/tp/sp(/ep/pp) model (models/transformer.py) trained for real on
a synthetic "repeat the pattern" language until the loss collapses.
Takes the devices jax finds (dp x tp x sp of them): on a TPU slice the
axes lie over ICI; for a CPU run give it a virtual mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_lm.py --steps 150
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def batch_tokens(rs, batch, seq, vocab):
    """Period-4 repeating patterns: predictable after one period."""
    pat = rs.randint(1, vocab, (batch, 4))
    reps = seq // 4 + 1
    return np.tile(pat, (1, reps))[:, :seq].astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--lr", type=float, default=None,
                    help="default 0.3 (0.1 with --rope: rotary logits "
                         "diverge under this plain momentum-SGD at 0.3)")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each layer (MXNET_BACKWARD_DO_MIRROR"
                         " analogue at transformer granularity)")
    ap.add_argument("--flash", action="store_true",
                    help="Pallas flash kernel for the per-shard ring "
                         "block compute (TPU)")
    ap.add_argument("--rope", action="store_true",
                    help="rotary positions instead of the learned table")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention KV heads (NOTE: this "
                         "toy induction task is capacity-sensitive — "
                         "halving KV heads can keep the loss above the "
                         "example's halving check)")
    args = ap.parse_args()
    if args.lr is None:
        args.lr = 0.1 if args.rope else 0.3

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.models import transformer as T

    need = args.dp * args.tp * args.sp
    if len(jax.devices()) < need:
        raise SystemExit(
            "mesh dp=%d x tp=%d x sp=%d needs %d devices, found %d — "
            "lower --dp/--tp/--sp, or run on CPU with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=%d"
            % (args.dp, args.tp, args.sp, need, len(jax.devices()), need))
    devs = np.array(jax.devices()[:need])
    mesh = Mesh(devs.reshape(args.dp, args.tp, args.sp),
                ("dp", "tp", "sp"))
    cfg = T.TransformerConfig(vocab_size=32, d_model=64, n_heads=4,
                              n_layers=2, d_ff=128, max_len=args.seq,
                              ep_axis=None,
                              rope=args.rope,
                              n_kv_heads=args.kv_heads or None,
                              remat_layers=args.remat,
                              use_flash_kernel=args.flash)
    with mesh:
        params = T.init_params(cfg, seed=0)
        params = T.shard_params(params, cfg, mesh)
        mom = T.init_momentum(params)
        step = T.make_train_step(cfg, mesh, lr=args.lr)
        rs = np.random.RandomState(0)
        first = None
        t0 = time.time()
        for i in range(args.steps):
            tokens = jnp.asarray(batch_tokens(rs, args.batch, args.seq,
                                              cfg.vocab_size))
            params, mom, loss = step(params, mom, tokens)
            if first is None:
                first = float(loss)
            if (i + 1) % 50 == 0:
                print("step %d loss %.4f" % (i + 1, float(loss)))
        final = float(loss)
    print("mesh %s: loss %.3f -> %.3f in %.1fs"
          % (dict(zip(mesh.axis_names, mesh.devices.shape)), first,
             final, time.time() - t0))
    assert final < first * 0.5
    print("LEARNED (loss halved)")


if __name__ == "__main__":
    main()
