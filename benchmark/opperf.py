"""Per-operator micro-benchmark harness.

Parity target: benchmark/opperf/ (opperf.py run_all_mxnet_operator
_benchmarks and the nd_operations/ suites). Times eager forward (and,
for differentiable ops, forward+backward through autograd) of registered
operators on standard shapes, reporting avg milliseconds after warmup.

    python benchmark/opperf.py                        # curated default set
    python benchmark/opperf.py --ops relu,dot,Convolution
    python benchmark/opperf.py --output-format json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _default_specs():
    """op name -> (positional array shapes, attrs). Shapes follow the
    reference's DEFAULT_* profiles (large 1024x1024-class tensors)."""
    big = (1024, 1024)
    conv_x = (32, 3, 64, 64)
    specs = {}
    for name in ("relu", "sigmoid", "tanh", "exp", "log", "sqrt", "abs",
                 "negative", "softrelu", "erf", "square"):
        specs[name] = ([big], {})
    for name in ("elemwise_add", "elemwise_mul", "elemwise_sub",
                 "elemwise_div", "broadcast_add", "broadcast_mul",
                 "maximum", "minimum"):
        specs[name] = ([big, big], {})
    specs["dot"] = ([big, big], {})
    specs["batch_dot"] = ([(32, 256, 256), (32, 256, 256)], {})
    specs["sum"] = ([big], {})
    specs["mean"] = ([big], {})
    specs["max"] = ([big], {})
    specs["argmax"] = ([big], {"axis": 1})
    specs["softmax"] = ([big], {})
    specs["log_softmax"] = ([big], {})
    specs["transpose"] = ([big], {})
    specs["FullyConnected"] = (
        [(64, 1024), (512, 1024), (512,)], {"num_hidden": 512})
    specs["Convolution"] = (
        [conv_x, (64, 3, 3, 3), (64,)],
        {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)})
    specs["Pooling"] = (
        [conv_x], {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"})
    specs["BatchNorm"] = (
        [conv_x, (3,), (3,), (3,), (3,)], {"fix_gamma": False,
                                           "is_train": True})
    specs["LayerNorm"] = ([big, (1024,), (1024,)], {})
    specs["Activation"] = ([big], {"act_type": "relu"})
    specs["Dropout"] = ([big], {"p": 0.5})
    specs["Concat"] = ([big, big], {"dim": 1})
    specs["Reshape"] = ([big], {"shape": (512, 2048)})
    return specs


def bench_op(name, shapes, attrs, runs=10, warmup=2, backward=True):
    from mxnet_tpu import nd, autograd
    from mxnet_tpu import ops as op_registry

    rng = np.random.RandomState(0)
    arrays = [nd.array(rng.uniform(0.5, 1.5, s).astype(np.float32))
              for s in shapes]
    fn = getattr(nd, name)

    def fwd():
        out = fn(*arrays, **attrs)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out

    for _ in range(warmup):
        float(fwd().asnumpy().ravel()[0])
    tic = time.time()
    for _ in range(runs):
        out = fwd()
    float(out.asnumpy().ravel()[0])
    fwd_ms = (time.time() - tic) / runs * 1e3

    result = {"op": name, "avg_fwd_ms": round(fwd_ms, 4),
              "shapes": [list(s) for s in shapes]}

    op = op_registry.get(name)
    if backward and op is not None and op.differentiable:
        for a in arrays:
            a.attach_grad()

        def step():
            with autograd.record():
                out = fn(*arrays, **attrs)
                if isinstance(out, (list, tuple)):
                    out = out[0]
                loss = out.sum() if out.dtype in ("float32", "float16")\
                    else out
            loss.backward()
            return arrays[0].grad

        for _ in range(warmup):
            float(step().asnumpy().ravel()[0])
        tic = time.time()
        for _ in range(runs):
            g = step()
        float(g.asnumpy().ravel()[0])
        result["avg_fwd_bwd_ms"] = round(
            (time.time() - tic) / runs * 1e3, 4)
    return result


def run_benchmarks(op_names=None, runs=10, warmup=2):
    specs = _default_specs()
    names = op_names or sorted(specs)
    results = []
    for name in names:
        if name not in specs:
            print("no default spec for op %r — skipping" % name,
                  file=sys.stderr)
            continue
        shapes, attrs = specs[name]
        try:
            results.append(bench_op(name, shapes, attrs, runs, warmup))
        except Exception as exc:            # keep the sweep alive
            results.append({"op": name, "error": str(exc)[:200]})
    return results


def dispatch_latency(iters=3000):
    """us/op small-op dispatch latency: where does an eager call's time
    go (SURVEY §3.1 — per-op dispatch is the reason CachedOp exists)?

    Ladder: raw jnp (jax's own dispatch floor) -> nd eager
    (imperative_invoke) -> nd eager under autograd.record (tape) ->
    CachedOp(add graph) -> bound executor forward. All on (4, 4)
    float32 so compute is negligible."""
    import time
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    a_j = jnp.ones((4, 4)); b_j = jnp.ones((4, 4))
    a = mx.nd.ones((4, 4)); b = mx.nd.ones((4, 4))

    def timeit(fn, sync):
        fn()  # warm (compile)
        sync()
        t0 = time.time()
        for _ in range(iters):
            out = fn()
        sync()
        return (time.time() - t0) / iters * 1e6

    from benchmark.common import fetch_barrier
    results = {}
    jadd = jax.jit(lambda x, y: x + y)
    results["raw_jnp_jit_add"] = timeit(
        lambda: jadd(a_j, b_j), lambda: fetch_barrier(jadd(a_j, b_j)))
    results["nd_eager_add"] = timeit(
        lambda: a + b, lambda: (a + b).wait_to_read())

    a.attach_grad()
    def rec():
        with autograd.record():
            return a + b
    results["nd_eager_add_recorded"] = timeit(
        rec, lambda: rec().wait_to_read())

    sa = mx.sym.Variable("a"); sb = mx.sym.Variable("b")
    graph = sa + sb
    cop = mx.nd.CachedOp(graph) if hasattr(mx.nd, "CachedOp") else None
    if cop is None:
        from mxnet_tpu.cached_op import CachedOp
        cop = CachedOp(graph)
    results["cached_op_add"] = timeit(
        lambda: cop(a, b)[0], lambda: cop(a, b)[0].wait_to_read())

    def cop_rec():
        with autograd.record():
            return cop(a, b)[0]
    results["cached_op_add_recorded"] = timeit(
        cop_rec, lambda: cop_rec().wait_to_read())

    ex = graph.bind(mx.cpu(), {"a": a, "b": b})
    results["executor_forward_add"] = timeit(
        lambda: ex.forward()[0], lambda: ex.forward()[0].wait_to_read())

    # a 20-op chain through CachedOp vs eager: amortization the reference
    # gets from graph replay (cached_op.cc DynamicForward)
    x = sa
    for _ in range(20):
        x = x + sb
    chain = x
    cop20 = type(cop)(chain)
    results["eager_chain20"] = timeit(
        lambda: sum20(a, b), lambda: sum20(a, b).wait_to_read())
    results["cached_op_chain20"] = timeit(
        lambda: cop20(a, b)[0], lambda: cop20(a, b)[0].wait_to_read())
    return results


def sum20(a, b):
    x = a
    for _ in range(20):
        x = x + b
    return x


def main():
    parser = argparse.ArgumentParser(
        description="operator micro-benchmarks",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--ops", type=str, default="",
                        help="comma-separated op names (default: all)")
    parser.add_argument("--runs", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--output-format", choices=("table", "json"),
                        default="table")
    parser.add_argument("--dispatch", action="store_true",
                        help="measure small-op dispatch latency (us/op)")
    args = parser.parse_args()

    if args.dispatch:
        res = dispatch_latency()
        for k, v in res.items():
            print(json.dumps({"metric": "dispatch_%s" % k,
                              "value": round(v, 1), "unit": "us/op"}))
        return

    names = [n for n in args.ops.split(",") if n] or None
    results = run_benchmarks(names, args.runs, args.warmup)
    if args.output_format == "json":
        print(json.dumps(results, indent=2))
    else:
        print("%-24s %12s %14s" % ("op", "fwd ms", "fwd+bwd ms"))
        for r in results:
            if "error" in r:
                print("%-24s ERROR %s" % (r["op"], r["error"][:60]))
            else:
                print("%-24s %12.4f %14s"
                      % (r["op"], r["avg_fwd_ms"],
                         ("%.4f" % r["avg_fwd_bwd_ms"])
                         if "avg_fwd_bwd_ms" in r else "—"))


if __name__ == "__main__":
    main()
