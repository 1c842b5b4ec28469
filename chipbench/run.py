"""The benchmark's one command:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one last line of standard output to the contract of
BENCHMARK.json. Everything that belongs to one configuration, traffic mix,
runner or per-layer metric is a file of its own, found by name (see
README.md); nothing here names a cell.
"""

import sys
import time

T_START = time.perf_counter()       # process start, as near as Python gets

import argparse                      # noqa: E402
import gc                            # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import math                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
from contextlib import contextmanager    # noqa: E402

from . import manifest as _manifest  # noqa: E402


class CompileClock(object):
    """Seconds and counts of JAX's compilations, from its own monitoring
    events (copied from chip_smoke.py). jax times compile-or-load together,
    so the cache's retrieval time is taken out of compile_s."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.backend = self.load = 0.0
        self.programs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event, secs, **_):
        if event == self.BACKEND:
            self.backend += secs
            self.programs += 1
        elif event == self.LOAD:
            self.load += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(devices):
    """Peak bytes on the fullest chip. On this runtime a program's
    temporaries are counted under `peak_bytes_reserved`, its arguments and
    results under `peak_bytes_in_use` (PERF.md, PR 24: the LM step reads
    3.4 GB in use and 11.9 GB reserved where the compiler's analysis says
    14.3 GB), so the peak is their sum."""
    def peak(d):
        s = d.memory_stats() or {}
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
    return max(peak(d) for d in devices)


class Tracer(object):
    """jax.profiler around a sub-window; the benchmark's own host spans go
    into the same trace as `bench.*` annotations."""

    def __init__(self, root, workload):
        self.dir = os.path.join(root, ".chipbench_out", "trace", workload)
        self.result = None

    @contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    @contextmanager
    def window(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            window = time.perf_counter() - t0
            jax.profiler.stop_trace()
        from . import trace
        self.result = dict(trace.reduce_dir(self.dir), window_s=window)
        shutil.rmtree(self.dir, ignore_errors=True)


# ------------------------------------------------------------- kinds ---

def _finite(values):
    return all(isinstance(v, float) and math.isfinite(v) for v in values)


def first_steps(session, steps=3):
    """What the comparison reads of the session's first steps: each loss,
    the first gradient's norms and the parameters' change, leaf by leaf."""
    got = {"losses": []}
    for i in range(steps):
        got["losses"].append(session.fetch(session.step()))
        if i == 0:
            got["grad_norms"] = session.first_grad_norms()
            if hasattr(session, "sample_losses"):
                got["sample_losses"] = session.sample_losses()
    got["delta_norms"] = session.delta_norms()
    return got


def run_train_steps(session, config, traffic, limits, args, tracer, note):
    """Set-up drives the session's own step through its first three steps
    (compared with the reference after the window), then the window runs
    the same object."""
    from . import compare
    from .traffic import run_train_steps as drive
    got = first_steps(session)
    drive(session, traffic, steps=traffic["fetch_every"])   # the fetch path
    setup_s = note("setup")
    seconds = args.seconds
    if tracer:
        seconds = max(args.seconds / 2.0,
                      args.seconds - traffic["trace_reserve_s"])
    w = drive(session, traffic, seconds=seconds)
    out = {"setup_s": setup_s, "clock": {"rate": w["items"] / w["window_s"],
                                         "step_ms": 1e3 * w["window_s"]
                                         / w["steps"]},
           "counters": {"steps": w["steps"]}, "attempted": w["steps"],
           "failed": 0 if _finite(w["losses"]) else w["steps"]}
    note("window")
    if tracer:
        with tracer.window():
            t = drive(session, traffic, steps=traffic["trace_steps"],
                      span=tracer.span)
        # the driver's own window, barrier to barrier, is the traced one
        tracer.result.update(steps=t["steps"], window_s=t["window_s"])
    out["memory_peak_bytes"] = _memory_peak(args.devices)
    session.release()
    gc.collect()
    t_ref = time.perf_counter()
    ref = session.reference()
    out["reference_s"] = time.perf_counter() - t_ref
    out["checks"] = compare.training_checks(got, ref, limits)
    return out


def run_closed_loop(session, config, traffic, limits, args, tracer, note):
    from . import compare
    from .traffic import (ClosedLoop, length_pool, request_stream,
                          sample_finished)
    session.warm([p for p, _ in length_pool(traffic)])
    loop = ClosedLoop(session, traffic,
                      request_stream(traffic, args.seed,
                                     config["vocab_size"]))
    # every lane turns over once before the window opens
    loop.run_until(turned_over=traffic["clients"],
                   give_up=time.perf_counter() + traffic["warm_max_s"])
    setup_s = note("setup")
    c0, t_open = session.counters(), time.perf_counter()
    measured = args.seconds - (traffic["trace_seconds"] if tracer else 0)
    loop.run_until(t_end=t_open + measured)
    c1, t_close = session.counters(), time.perf_counter()
    note("window")
    m = loop.reduce(t_open, t_close)
    if tracer:
        loop.span = tracer.span
        with tracer.window():
            loop.run_until(t_end=time.perf_counter()
                           + traffic["trace_seconds"])
        finished = loop.reduce(t_open, time.perf_counter())["finished"]
    else:
        finished = m["finished"]
    out = {"setup_s": setup_s,
           "clock": {"rate": m["tok_s"], "itl_p95_ms": m["itl_p95_ms"],
                     "itl_p50_ms": m["itl_p50_ms"],
                     "ttft_p50_ms": m["ttft_p50_ms"]},
           "counters": {"tokens": m["tokens"], "gaps": m["gaps"],
                        "admitted": m["admitted"],
                        "dispatches": c1["dispatches"] - c0["dispatches"]},
           "memory_peak_bytes": _memory_peak(args.devices)}
    malformed = sum(
        1 for r in finished
        if len(r["tokens"]) != len(r["prompt"]) + r["n_new"]
        or list(r["tokens"][: len(r["prompt"])]) != list(r["prompt"]))
    out["attempted"] = len(finished) + len(loop.live)
    out["failed"] = malformed
    sample = sample_finished(finished, args.seed, traffic["check_requests"])
    session.release()
    gc.collect()
    t_ref = time.perf_counter()
    gaps = session.reference([(len(r["prompt"]), r["tokens"])
                              for r in sample])
    out["reference_s"] = time.perf_counter() - t_ref
    out["checks"] = compare.serving_checks(
        [g["gaps"] for g in gaps], malformed, len(finished), limits)
    return out


KINDS = {"train-steps": run_train_steps, "closed-loop": run_closed_loop}


def kind_of(traffic):
    """The function that runs a traffic kind. A kind this file does not
    know names its own in the traffic file, `"driver": "<module>:<name>"`
    under chipbench, so that a new kind is a new file."""
    if traffic["kind"] in KINDS:
        return KINDS[traffic["kind"]]
    module, name = traffic["driver"].split(":")
    return getattr(importlib.import_module("chipbench." + module), name)


# -------------------------------------------------------------- a run ---

def run_cell(man, cell, args, config=None, traffic=None, limits=None,
             log=sys.stderr):
    """Everything of a run after the look for a chip. Returns the result
    object of the last line. `config`/`traffic`/`limits` default to the
    cell's files (the tests pass tiny ones)."""
    import jax
    config = config or man.config_of(cell)
    traffic = traffic or man.traffic_of(cell)
    limits = limits or _manifest.load_limits(cell["name"], man.root)
    clock = CompileClock()
    marks = {}

    def note(what):
        marks[what] = (time.perf_counter() - T_START, clock.programs)
        return marks[what][0]

    args.devices = jax.devices()[: cell["chips"]]
    note("devices")
    runner = importlib.import_module(
        "chipbench.runners." + config["runner"])
    session = runner.build(config, traffic, args.seed)
    note("built")
    tracer = Tracer(man.root, cell["name"]) if args.trace else None
    out = kind_of(traffic)(session, config, traffic, limits, args, tracer,
                           note)
    in_window = marks["window"][1] - marks["setup"][1]
    from . import compare
    out["checks"].append(compare.check(
        "compilations_in_window", float(in_window), 0.0))
    compare.print_checks(out["checks"], log)
    log.write("stages: devices_s=%.2f built_s=%.2f setup_s=%.2f "
              "window_end_s=%.2f\n" % tuple(
                  marks[k][0] for k in ("devices", "built", "setup",
                                        "window")))
    log.write("setup: compile_s=%.2f cache_load_s=%.2f programs=%d "
              "cache_hits=%d cache_misses=%d peak_bytes_in_use=%d "
              "reference_s=%.2f\n" % (
                  clock.backend - clock.load, clock.load,
                  marks["setup"][1], clock.hits, clock.misses,
                  out["memory_peak_bytes"], out["reference_s"]))
    log.write("memory_stats: %s\n" % json.dumps(
        args.devices[0].memory_stats() or {}))
    device = dict(_device(), memory_peak_bytes=out["memory_peak_bytes"])
    ctx = {"config": config, "traffic": traffic, "device": device,
           "chips": cell["chips"],
           "clock": out["clock"], "counters": out["counters"],
           "trace": tracer.result if tracer else None}
    result = {"correct": all(c["ok"] for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": {}, "device": device}
    if tracer:
        device["busy_s"] = tracer.result["busy_s"]
        device["window_s"] = tracer.result["window_s"]
        result["breakdown"] = {"device_ops": tracer.result["device_ops"],
                               "idle_gaps": tracer.result["idle_gaps"]}
        for m in man.metrics_of(cell, "per_layer"):
            spec = _manifest.load_layer_metric(m["name"], man.root)
            reader = importlib.import_module(
                "chipbench.readers." + spec["reader"])
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        end = dict(traffic["end_to_end"])    # metric name -> clock key
        for m in man.metrics_of(cell, "end_to_end"):
            value = out["setup_s"] if m["name"] == "setup_s" \
                else out["clock"].get(end.get(m["name"]))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        man = _manifest.Manifest()
        cell = man.cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        sys.stderr.write("chipbench: %s\n" % e)
        return 2
    try:
        from mxnet_tpu import chip
        dev = chip.require_accelerator("chipbench")
        if dev["count"] < cell["chips"]:
            raise RuntimeError("cell %s needs %d chip(s), jax found %d"
                               % (cell["name"], cell["chips"], dev["count"]))
        import jax
        chip.use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception as e:      # noqa: BLE001 - no chip, no program: no run
        sys.stderr.write("chipbench: %s; nothing was run\n" % e)
        return 2
    result = run_cell(man, cell, args)
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
