"""Readings a limit is set from (run by hand on the chip, not by the
benchmark's own runs):

    python3 -m chipbench.calibrate --workload <name> --seeds 1 2 3 ... [--control-seeds 1 2 3] [--seconds 25]

For each seed: the numbers `correct` compares, for the program against the
float32 reference and, on --control-seeds, for the control (the reference
computed in float8, the step below bfloat16) against the same reference.
One process reads all seeds, so the programs compile once. One JSON line a
seed, and a last line with the largest sound reading and the smallest
control reading of each number. See PERF.md section 2 for the limits set
from them."""

import argparse
import gc
import importlib
import json
import sys
import time

from . import compare, manifest
from .run import first_steps

INF = float("inf")


def _values(checks):
    return {c["name"]: c["value"] for c in checks}


def _training(got, ref):
    """The compared numbers under every statistic over the leaves."""
    out = {}
    for stat in ("worst", "median", "p90"):
        lim = {"loss_gap": INF, "grad_norm_gap": {"stat": stat, "limit": INF},
               "delta_norm_gap": {"stat": stat, "limit": INF}}
        if "sample_losses" in got:
            lim["sample_loss_gap"] = INF
        for k, v in _values(compare.training_checks(got, ref, lim)).items():
            out[k if k.endswith("loss_gap") else "%s.%s" % (k, stat)] = v
    out["loss_gap_step1"] = abs(got["losses"][0] - ref["losses"][0]) \
        / abs(ref["losses"][0])
    return out


def train_seed(runner, config, traffic, seed, control, mode):
    session = runner.build(config, traffic, seed)
    got = first_steps(session)
    session.release()
    gc.collect()
    ref = session.reference()
    out = {"seed": seed,
           "program": _training(got, ref),
           "losses": got["losses"], "ref_losses": ref["losses"]}
    if control:
        out["control"] = _training(session.reference(mode), ref)
    return out


def serve_seed(runner, config, traffic, seed, control, mode, seconds):
    from .traffic import (ClosedLoop, length_pool, request_stream,
                          sample_finished)
    session = runner.build(config, traffic, seed)
    session.warm([p for p, _ in length_pool(traffic)])
    loop = ClosedLoop(session, traffic,
                      request_stream(traffic, seed, config["vocab_size"]))
    t0 = time.perf_counter()
    loop.run_until(t_end=t0 + seconds)
    m = loop.reduce(t0, time.perf_counter())
    sample = sample_finished(m["finished"], seed, traffic["check_requests"])
    session.release()
    gc.collect()
    gaps = session.reference([(len(r["prompt"]), r["tokens"])
                              for r in sample],
                             operand=mode if control else None)
    flat = [g for s in gaps for g in s["gaps"]]
    out = {"seed": seed, "tok_s": m["tok_s"], "finished": len(m["finished"]),
           "tokens_compared": len(flat),
           "program": {"served_logit_gap": max(flat)},
           "program_mean_gap": sum(flat) / len(flat),
           "program_tokens_off_best": sum(1 for g in flat if g > 0)}
    if control:
        cflat = [g for s in gaps for g in s["control_gaps"]]
        out["control"] = {"served_logit_gap": max(cflat)}
        out["control_mean_gap"] = sum(cflat) / len(cflat)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args()
    from mxnet_tpu import chip
    chip.require_accelerator("chipbench.calibrate")
    chip.use_compile_cache()
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    config, traffic = man.config_of(cell), man.traffic_of(cell)
    runner = importlib.import_module("chipbench.runners." + config["runner"])
    mode = manifest.load_limits(cell["name"], man.root)["control"]
    sound, low = {}, {}
    for seed in args.seeds:
        control = seed in args.control_seeds
        if traffic["kind"] == "train-steps":
            out = train_seed(runner, config, traffic, seed, control,
                             mode)
        else:
            out = serve_seed(runner, config, traffic, seed, control,
                             mode, args.seconds)
        for k, v in out["program"].items():
            sound[k] = max(sound.get(k, 0.0), v)
        for k, v in out.get("control", {}).items():
            low[k] = min(low.get(k, float("inf")), v)
        print(json.dumps(out))
        sys.stdout.flush()
    print(json.dumps({"largest_sound": sound, "smallest_control": low}))


if __name__ == "__main__":
    main()
