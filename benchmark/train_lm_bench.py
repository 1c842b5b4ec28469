"""Single-chip LM training throughput: tokens/s and MFU.

Trains the transformer flagship (flash attention + per-layer remat,
bf16) for timed windows and reports tokens/s plus model-FLOPs
utilization (6*N*tokens / peak). This is a capability benchmark the
reference cannot express (its transformer surface stops at helper
ops); the matmul-dominated LM step is also the best single number for
"how well does the stack feed the MXU".

    python - < benchmark/train_lm_bench.py
    MXNET_LM_SMOKE=1 JAX_PLATFORMS=cpu python - < benchmark/train_lm_bench.py

Env knobs: MXNET_LM_DMODEL/LAYERS/SEQ/BATCH/STEPS override the model.
Run from the repo root via stdin so cwd lands on sys.path.

MXNET_LM_COST=1 skips timing and instead prints XLA's own cost model
for the compiled step (FLOPs + bytes accessed) and the roofline MFU
it predicts — the attribution tool for a measured-MFU gap: if the
measured number matches the bytes-predicted ceiling, the shape is
bandwidth-bound and the fix is arithmetic intensity (layout/fusion),
not scheduling. Runs on any backend (CPU fusion differs slightly from
TPU's; treat bytes as an estimate).
"""

import json
import os
import time

import numpy as np

SMOKE = bool(os.environ.get("MXNET_LM_SMOKE"))


def _env_int(name, default):
    return int(os.environ.get(name, default))


def main():
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    # --obs-ops (docs/OBSERVABILITY.md): sets MXNET_OBS before anything
    # traces, so the step program lands in the attribution registry
    from benchmark.common import obs_ops_requested, print_ops_table
    obs_ops = obs_ops_requested()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import chip
    from mxnet_tpu.models import transformer as tf

    # peaks of the device this process met, by device_kind (an unknown
    # chip is an error; a CPU-pinned smoke models the v5e and prints no
    # MFU from its timings)
    dev = chip.describe()
    peak = chip.peaks()

    if SMOKE:
        d_model, layers, seq, batch, steps = 32, 1, 64, 2, 2
    else:
        # MXU-bound defaults (VERDICT r4 #3): d_model>=1024, seq 1024,
        # flash attention on, remat OFF — remat trades FLOPs for HBM,
        # which depresses measured MFU; it stays available as a knob
        # for memory-limited shapes
        d_model = _env_int("MXNET_LM_DMODEL", 1024)
        layers = _env_int("MXNET_LM_LAYERS", 12)
        seq = _env_int("MXNET_LM_SEQ", 1024)
        batch = _env_int("MXNET_LM_BATCH", 8)
        steps = _env_int("MXNET_LM_STEPS", 10)
    remat = _env_int("MXNET_LM_REMAT", 1 if SMOKE else 0) == 1
    # unset -> the backend default (flash on real TPU); set -> same
    # string convention as MXNET_DECODE_FLASH ('0'/'false' disable)
    flash_env = os.environ.get("MXNET_LM_FLASH")
    use_flash = (jax.default_backend() == "tpu" if flash_env is None
                 else flash_env.lower() not in ("0", "false", ""))

    cfg = tf.TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=max(2, d_model // 128),
        n_layers=layers, d_ff=4 * d_model, max_len=seq,
        dtype=jnp.bfloat16, rope=True,
        use_flash_kernel=use_flash,
        remat_layers=remat)
    params = tf.init_params(cfg, seed=0)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    step = tf.make_train_step(cfg)
    mom = tf.init_momentum(params)
    if obs_ops:
        # the LM step is a raw jitted fn (no CachedOp/Executor in the
        # path) — register it by hand so --obs-ops can break it down
        from mxnet_tpu.observability import attribution, recompile
        attribution.register_program(
            "train_lm.step",
            recompile.signature_of(jax.tree.leaves((params, mom))),
            step, (params, mom,
                   jnp.zeros((batch, seq), jnp.int32)))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, 32000, (batch, seq)), jnp.int32)
    tokens_per_step = batch * seq
    # standard decoder-only accounting: ~6*N FLOPs per trained token
    # (fwd 2N + bwd 4N); attention FLOPs excluded, so MFU is slightly
    # conservative at long seq
    flops_per_step = 6.0 * n_params * tokens_per_step

    if os.environ.get("MXNET_LM_COST"):
        # roofline attribution from the compiler's own cost model
        lowered = jax.jit(lambda p, m, t: step(p, m, t)).lower(
            params, mom, tokens)
        from mxnet_tpu.observability.hlo import compiled_cost
        compiled = lowered.compile()
        ca = compiled_cost(compiled)
        xla_flops = float(ca.get("flops", 0.0))
        bytes_acc = float(ca.get("bytes accessed", 0.0))
        if not xla_flops and not bytes_acc:
            print(json.dumps({"metric": "lm_train_cost_model",
                              "error": "cost analysis unavailable on "
                                       "backend %s"
                                       % jax.default_backend()}))
            return
        hbm_bw = peak.hbm_bytes_per_s
        t_flops = xla_flops / peak.bf16_flops
        t_bytes = bytes_acc / hbm_bw
        bound = "compute" if t_flops >= t_bytes else "bandwidth"
        pred = flops_per_step / (max(t_flops, t_bytes) * peak.bf16_flops)
        print(json.dumps({
            "metric": "lm_train_cost_model", "d_model": d_model,
            "layers": layers, "seq": seq, "batch": batch,
            "remat": remat, "flash": use_flash,
            "params_m": round(n_params / 1e6, 1),
            "xla_flops_g": round(xla_flops / 1e9, 1),
            "model_flops_6n_g": round(flops_per_step / 1e9, 1),
            "bytes_accessed_gb": round(bytes_acc / 1e9, 3),
            "intensity_flop_per_byte": round(xla_flops
                                             / max(bytes_acc, 1), 1),
            "bound": bound,
            "roofline_mfu": round(min(pred, 1.0), 4),
            "assumed_hbm_gbs": hbm_bw / 1e9,
        }))
        if obs_ops:
            print_ops_table(compiled)
        return

    params, mom, loss = step(params, mom, tokens)    # compile + warm
    float(loss)
    params, mom, loss = step(params, mom, tokens)
    float(loss)

    rates = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            params, mom, loss = step(params, mom, tokens)
        loss = float(loss)                           # full barrier
        rates.append(tokens_per_step * steps / (time.time() - t0))
    rate = float(np.median(rates))
    # a utilization is a device number: none from a CPU run
    mfu = (round(flops_per_step * rate / tokens_per_step
                 / peak.bf16_flops, 4)
           if dev["platform"] != "cpu" else None)
    print(json.dumps({
        "metric": "lm_train_tokens_per_s_%s" % jax.default_backend(),
        "value": round(rate, 1), "unit": "tokens/s",
        "platform": dev["platform"], "device_kind": dev["kind"],
        "device_count": dev["count"],
        "params_m": round(n_params / 1e6, 1),
        "d_model": d_model, "layers": layers, "seq": seq,
        "batch": batch, "remat": remat, "flash": use_flash,
        "mfu": mfu,
        "mfu_peak_flops": peak.bf16_flops,
        "loss_finite": bool(np.isfinite(loss)),
    }))
    from benchmark.common import record_bench_profile
    record_bench_profile(
        "train_lm", value=round(rate, 1), unit="tokens/s",
        metric="lm_train_tokens_per_s_%s" % jax.default_backend(),
        d_model=d_model, layers=layers, seq=seq, batch=batch,
        remat=remat, flash=use_flash, mfu=mfu)
    # the aggregate table below already appends the per-operator
    # attribution section when --obs-ops registered the step program
    from benchmark.common import print_obs_table
    print_obs_table()


if __name__ == "__main__":
    main()
