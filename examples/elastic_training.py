"""Failure recovery for SPMD training: crash, relaunch, resume — and
the elastic shrink/regrow worker.

The baseline contract (SURVEY §5): a training run checkpoints every
--ckpt-every steps (models/checkpoint.py: manifest-commit atomicity, so
a crash can never leave a half-written checkpoint), the process is
killed mid-run, and a relaunch picks up from the last committed step —
landing on EXACTLY the parameters the uninterrupted run produces.

    python examples/elastic_training.py --demo      # full crash/resume story
    python examples/elastic_training.py --steps 8   # one (resumable) run

``--elastic-worker`` is the stronger story (docs/ROBUSTNESS.md
"Elastic recovery"): one generation of a multi-process elastic job
driven by ``tools/elastic_launch.py``. The worker heartbeats through
the ``MXNET_ELASTIC_DIR`` sideband, detects dead peers, and on a
death captures its survivor-side shard checkpoint (weights + local
optimizer slice + exact data cursor + RNG) before leaving with exit
44 so the supervisor relaunches the survivors at generation g+1:

    python tools/elastic_launch.py -n 2 -- \
        python examples/elastic_training.py --elastic-worker --steps 6

The worker run is restartable by construction: it always tries to
resume from --ckpt-dir first, so a supervisor (shell loop, k8s restart
policy) that relaunches the same command line IS the recovery system.
"""

import argparse
import os
import subprocess
import sys
import time as _time

import numpy as np

# runnable from anywhere: the repo root is the package root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.models import transformer as T

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2, "ep": 1})
    cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_len=16)
    rng = np.random.RandomState(0)
    tokens = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, (8, cfg.max_len)),
                    jnp.int32),
        NamedSharding(mesh, P("dp", None)))
    return mesh, cfg, tokens


def worker(args):
    """One (re)startable training run: resume from the newest loadable
    checkpoint (corrupt ones fall back — docs/ROBUSTNESS.md), train to
    --steps, checkpoint every --ckpt-every retaining the previous one,
    optionally crash hard after the step --crash-after. A SIGTERM
    (preemption notice) commits a best-effort emergency checkpoint of
    the CURRENT step before exiting."""
    import jax
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.checkpoint import (
        save_checkpoint, resume_from_latest,
        install_emergency_checkpoint)

    mesh, cfg, tokens = build(args)

    def fresh():
        p = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
        return cfg, p, T.shard_params(T.init_momentum(p), cfg, mesh), 0

    cfg, params, mom, start = resume_from_latest(args.ckpt_dir, mesh,
                                                 init=fresh)
    if start:
        print("resumed from step %d" % start, flush=True)

    live = {"params": params, "mom": mom, "step": start}
    install_emergency_checkpoint(
        args.ckpt_dir,
        lambda: {"cfg": cfg, "params": live["params"],
                 "momentum": live["mom"], "step": live["step"]})

    step_fn = T.make_train_step(cfg, mesh, lr=0.1)
    for step in range(start + 1, args.steps + 1):
        params, mom, loss = step_fn(params, mom, tokens)
        live.update(params=params, mom=mom, step=step)
        if step % args.ckpt_every == 0 or step == args.steps:
            save_checkpoint(args.ckpt_dir, cfg, params, momentum=mom,
                            step=step, keep=2)
        print("step %d loss %.5f" % (step, float(loss)), flush=True)
        if args.crash_after is not None and step >= args.crash_after:
            print("simulating crash (SIGKILL semantics)", flush=True)
            os._exit(17)
    # report the final state fingerprint so runs can be compared
    digest = float(sum(jax.numpy.abs(l).sum()
                       for l in jax.tree.leaves(params)))
    print("final step %d param_l1 %.6f" % (args.steps, digest),
          flush=True)


def elastic_worker(args):
    """One generation of an elastic job (tools/elastic_launch.py).

    Deterministic by construction so the correctness bar is testable:
    a fixed 64-row token set consumed through an NDArrayIter cursor (8
    rows per optimizer step regardless of world size), the same tiny
    flagship config everywhere, and a non-donating train step so the
    survivor-side monitor thread can always capture the last COMPLETED
    step's state. Emits machine-checkable lines:

        LOSS g<gen> r<rank> <step> <float hex>
        DATA g<gen> r<rank> <step> <row_lo> <row_hi>
        TTR <ms>                        (first step after a recovery)
    """
    import numpy as np
    from mxnet_tpu import io as mx_io, parallel, profiler
    from mxnet_tpu.parallel import elastic
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models import checkpoint as C
    from mxnet_tpu.observability import chaos

    parallel.init_distributed()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rank, world = elastic.rank_env(), elastic.world_env()
    gen = elastic.generation_env()
    base_world = int(os.environ.get("MXNET_ELASTIC_BASE_WORLD", world))
    mesh = parallel.make_mesh({"dp": -1, "tp": 1, "sp": 1, "ep": 1})
    cfg = T.TransformerConfig(vocab_size=41, d_model=16, n_heads=2,
                              n_layers=1, d_ff=32, max_len=32)
    accum = elastic.accumulation_factor(base_world, world) \
        if elastic.keep_global_batch() else 1
    rows = 8                               # global rows per step, fixed

    def fresh():
        p = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
        m = T.shard_params(T.init_momentum(p), cfg, mesh)
        return cfg, p, m, 0, {}

    resume_gen = os.environ.get("MXNET_ELASTIC_RESUME_GEN")
    _, params, mom, start, extras = C.resume_elastic(
        args.ckpt_dir, mesh, init=fresh, expect_generation=gen,
        allow_partial=args.allow_partial,
        generation=int(resume_gen) if resume_gen else None)
    data = np.random.RandomState(7).randint(
        0, cfg.vocab_size, (64, cfg.max_len)).astype(np.int32)
    it = mx_io.NDArrayIter(data, batch_size=rows,
                           last_batch_handle="discard")
    if extras.get("cursor"):
        it.load_state_dict(elastic.cursor_from_json(extras["cursor"]))
    if extras.get("rng"):
        elastic.restore_rng(extras["rng"])
    ttr = elastic.observe_recovery()
    if ttr is not None and rank == 0:
        print("TTR %.1f" % ttr, flush=True)
    if start:
        print("resumed g%d r%d from step %d (world %d, accum %d)"
              % (gen, rank, start, world, accum), flush=True)

    live = {"params": params, "mom": mom, "step": start,
            "cursor": it.state_dict()}

    def provider():
        return {"cfg": cfg, "params": live["params"],
                "momentum": live["mom"], "step": live["step"],
                "cursor": elastic.jsonable_cursor(live["cursor"]),
                "rng": elastic.capture_rng(),
                "metadata": {"elastic": {"generation": gen,
                                         "world": world}}}

    coord = None
    if elastic.enabled() and world > 1:
        coord = elastic.install_coordinator(
            elastic.ElasticCoordinator(args.ckpt_dir, provider))
    C.install_emergency_checkpoint(args.ckpt_dir, provider,
                                   on_watchdog=False)

    def save_shard(step):
        C.save_shard_checkpoint(
            args.ckpt_dir, cfg, live["params"], momentum=live["mom"],
            step=step, rank=rank, world=world, generation=gen + 1,
            cursor=elastic.jsonable_cursor(live["cursor"]),
            rng=elastic.capture_rng(), base_world=base_world)

    step_fn = elastic.make_accum_train_step(cfg, mesh, lr=0.1,
                                            accum=accum)
    gen_steps = 0
    for step in range(start + 1, args.steps + 1):
        row_lo = int(it.cursor) + rows      # rows this batch will take
        batch = it.next().data[0].asnumpy().astype(np.int32)
        micro = batch.reshape(accum, rows // accum, cfg.max_len)
        tokens = jax.make_array_from_callback(
            micro.shape, NamedSharding(mesh, P(None, "dp", None)),
            lambda idx: micro[idx])
        try:
            params, mom, loss = step_fn(params, mom, tokens)
            loss_val = float(loss)          # sync: the step COMPLETED
        except Exception:
            # a gloo peer dying can surface as a collective error
            # instead of a hang: the error is evidence, but membership
            # is decided by heartbeats — poll out the staleness window
            # before concluding, so detection never races the signal
            if coord is not None:
                deadline = _time.time() + elastic.heartbeat_s() \
                    * (elastic.miss_threshold() + 2)
                while _time.time() < deadline:
                    dead = coord.dead()
                    if dead:
                        coord.shrink(dead)  # exits 44
                    _time.sleep(elastic.heartbeat_s() / 2)
            raise
        # print BEFORE publishing the step to the capture provider: a
        # shrink landing in between then resumes from the PREVIOUS
        # step and deterministically re-produces this step's lines,
        # instead of silently losing them (at-least-once logging; the
        # update itself is applied exactly once either way)
        print("DATA g%d r%d %d %d %d" % (gen, rank, step, row_lo,
                                         row_lo + rows), flush=True)
        print("LOSS g%d r%d %d %s" % (gen, rank, step,
                                      loss_val.hex()), flush=True)
        live.update(params=params, mom=mom, step=step,
                    cursor=it.state_dict())
        if coord is not None:
            coord.beat(step)
            coord.check()
        chaos.fire("train.step", step=step)   # injected kills land here
        gen_steps += 1
        if step < args.steps and args.gen_steps \
                and gen_steps >= args.gen_steps and world < base_world:
            # generation boundary: hand back so the recovered host can
            # rejoin; the shard set at g+1 carries the exact cursor
            save_shard(step)
            print("boundary g%d r%d at step %d" % (gen, rank, step),
                  flush=True)
            _dump_trace(profiler, gen)
            if coord is not None:
                coord.leave_at_boundary()
            sys.exit(elastic.BOUNDARY_EXIT_CODE)
    save_shard(args.steps)
    if coord is not None:
        coord.stop()            # disarm shrink: this rank is DONE
    C.uninstall_emergency_checkpoint()
    _dump_trace(profiler, gen)
    digest = float(sum(abs(l).sum() for l in jax.tree.leaves(params)))
    print("final g%d r%d step %d param_l1 %.6f"
          % (gen, rank, args.steps, digest), flush=True)


def _dump_trace(profiler, gen):
    """Per-generation chrome trace (rank-suffixed) into the sideband
    dir, so the merged trace carries the recovery histogram."""
    from mxnet_tpu.parallel import elastic
    from mxnet_tpu.observability import core as _obs
    d = elastic.elastic_dir()
    if not d or not _obs.enabled():
        return
    try:
        profiler.set_config(filename=os.path.join(
            d, "trace-g%d.json" % gen), xla_trace=False)
        profiler.dump()
    except Exception:
        pass


def demo(args):
    """Crash a run mid-training, relaunch it, and check the resumed
    trajectory matches an uninterrupted one exactly."""
    import shutil
    import tempfile
    base = [sys.executable, os.path.abspath(__file__),
            "--steps", "6", "--ckpt-every", "2"]
    env = dict(os.environ)
    work = tempfile.mkdtemp(prefix="elastic_")
    try:
        clean = os.path.join(work, "clean")
        crashy = os.path.join(work, "crashy")
        ref = subprocess.run(base + ["--ckpt-dir", clean], env=env,
                             capture_output=True, text=True)
        assert ref.returncode == 0, ref.stderr
        crash = subprocess.run(
            base + ["--ckpt-dir", crashy, "--crash-after", "3"],
            env=env, capture_output=True, text=True)
        assert crash.returncode == 17, (crash.returncode, crash.stderr)
        resume = subprocess.run(base + ["--ckpt-dir", crashy], env=env,
                                capture_output=True, text=True)
        assert resume.returncode == 0, resume.stderr
        assert "resumed from step 2" in resume.stdout, resume.stdout

        final = [ln for out in (ref.stdout, resume.stdout)
                 for ln in out.splitlines() if ln.startswith("final ")]
        print("\n".join(["uninterrupted: " + final[0],
                         "crash+resume:  " + final[1]]))
        assert final[0] == final[1], "resumed run diverged"
        print("OK: crash + relaunch reproduces the uninterrupted run")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--elastic-worker", action="store_true",
                    help="run one generation of an elastic job "
                         "(driven by tools/elastic_launch.py)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--gen-steps", type=int, default=2,
                    help="elastic: steps per generation before a "
                         "boundary hand-back while shrunk")
    ap.add_argument("--allow-partial", action="store_true",
                    help="elastic: zero-fill unrecoverable optimizer "
                         "slices instead of failing the resume")
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="./elastic_ckpt")
    ap.add_argument("--crash-after", type=int, default=None)
    args = ap.parse_args()
    if args.demo:
        demo(args)
        return
    if args.elastic_worker:
        # the launcher exported JAX_PLATFORMS/XLA_FLAGS already;
        # init_distributed() pins the platform before backend init
        elastic_worker(args)
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # the flag only widens the host platform (a CPU run then has the 8
    # devices the mesh needs); the platform itself is whatever jax finds
    worker(args)


if __name__ == "__main__":
    main()
