"""Attribute the framework-vs-hand-built byte gap instruction by
instruction.

cost_compare's timed chip A/B (PERF.md "Chip numbers of 2026-08-01",
cost_compare_timed — a claim until re-measured) shows
the shipped framework ResNet-50 step moving ~10 GB/step more than the
hand-built jax step at the same shapes — bytes, not flops. XLA's
cost_analysis() only gives totals, so this script compiles BOTH steps
for the attached backend and breaks the optimized HLO down per
instruction and per source scope.

This is now a THIN WRAPPER over ``mxnet_tpu.observability.hlo`` — the
parser/accounting that used to live here was promoted into the
observability attribution layer (ISSUE 4), so this script, the
per-operator attribution tables (``tools/obs_ops.py``) and the
perf-regression sentinel all read the same numbers and cannot drift.
The accounting model (HBM bytes = output + operand outputs at fusion
boundaries; shape-derived flops) is documented in that module's
docstring.

    python - < benchmark/hlo_diff.py                 # both legs, diff
    python - framework < benchmark/hlo_diff.py
    python - handbuilt < benchmark/hlo_diff.py
    python - serving < benchmark/hlo_diff.py         # gather vs kernel

The ``serving`` mode diffs the paged decode step with
MXNET_PAGED_DECODE_PALLAS off (fused-XLA gather feeding the dense
contraction) vs on (the kernels/paged_decode.py batched-lane Pallas
kernel) at a small int8-KV GQA shape — so a byte-count regression in
the gather path is attributable per opcode and per scope, and the
kernel's custom-call shows up against the gather/dynamic-slice bytes
it removes. Shape knobs: MXNET_HLO_SERVING_SLOTS / _MAXLEN / _DMODEL.

Run from /root/repo via stdin so the repo root stays on sys.path.
"""

import os
import sys
from collections import defaultdict

import numpy as np

BATCH = int(os.environ.get("MXNET_COST_BATCH", "128"))
SIZE = int(os.environ.get("MXNET_COST_SIZE", "224"))
TOP = int(os.environ.get("MXNET_HLO_TOP", "25"))


def summarize(tag, rows):
    """Per-opcode byte totals + the top individual instructions + a
    per-scope rollup (scope names from the op_name metadata XLA
    preserves; the framework leg gets block names when MXNET_OBS was
    on at trace time, both legs get the heuristic path split)."""
    from mxnet_tpu.observability import hlo

    agg = defaultdict(lambda: [0, 0])
    total = 0
    for r in rows:
        if r["opcode"] in hlo.SKIP_OPCODES:
            continue
        agg[r["opcode"]][0] += r["accessed"]
        agg[r["opcode"]][1] += 1
        total += r["accessed"]
    print("\n== %s: %.1f GB estimated accessed ==" % (tag, total / 1e9))
    for op, (b, n) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        if b < 5e7:
            continue
        print("  %-24s %8.2f GB  x%d" % (op, b / 1e9, n))
    print("  -- top instructions --")
    top = sorted((r for r in rows if r["opcode"] not in hlo.SKIP_OPCODES),
                 key=lambda r: -r["accessed"])[:TOP]
    for r in top:
        print("  %7.1f MB  %-12s %s" % (
            r["accessed"] / 1e6, r["opcode"], r["op_name"][-90:]))
    scopes, totals = hlo.group_by_scope(rows)
    print("  -- per-scope (top 10 by bytes) --")
    for scope, ent in sorted(scopes.items(),
                             key=lambda kv: -kv[1]["hbm_bytes"])[:10]:
        print("  %7.1f MB  %8.2f GFLOP  %s" % (
            ent["hbm_bytes"] / 1e6, ent["flops"] / 1e9, scope[-70:]))
    return agg, total


def main():
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import importlib.util
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import hlo

    spec = importlib.util.spec_from_file_location(
        "cost_compare", os.path.join("benchmark", "cost_compare.py"))
    cc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cc)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(BATCH, 3, SIZE, SIZE).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (BATCH,)), jnp.int32)
    which = [a for a in sys.argv[1:] if a in ("framework", "handbuilt")]

    results = {}
    if not which or "framework" in which:
        import bench
        step, args, mom, aux = bench.build_train_step(BATCH, SIZE)
        c = step.lower(args, mom, aux, x, y).compile()
        results["framework"] = summarize(
            "framework", hlo.parse_hlo(c.as_text()))
    if not which or "handbuilt" in which:
        step, params, mom = cc.hb_build(BATCH, SIZE)
        c = step.lower(params, mom, x, y).compile()
        results["handbuilt"] = summarize(
            "handbuilt", hlo.parse_hlo(c.as_text()))

    if len(results) == 2:
        fa, ft = results["framework"]
        ha, ht = results["handbuilt"]
        print("\n== diff (framework - handbuilt) ==")
        print("  total: %+.1f GB" % ((ft - ht) / 1e9))
        ops = set(fa) | set(ha)
        for op in sorted(ops, key=lambda o: -(fa[o][0] - ha[o][0])):
            d = fa[op][0] - ha[op][0]
            if abs(d) < 5e7:
                continue
            print("  %-24s %+8.2f GB  (x%d vs x%d)" % (
                op, d / 1e9, fa[op][1], ha[op][1]))


def serving():
    """Kernel-off vs kernel-on serving HLO at one small paged shape.

    Both programs are the REAL entry point (decode_step_paged under
    jit, int8-KV + GQA + block tables); the only variable is the
    MXNET_PAGED_DECODE_PALLAS flag at trace time. The diff row set is
    what the serving_megakernel bench leg's GB/step numbers roll up
    from, instruction by instruction."""
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import hlo
    from mxnet_tpu.models import transformer as tf

    slots = int(os.environ.get("MXNET_HLO_SERVING_SLOTS", "8"))
    max_len = int(os.environ.get("MXNET_HLO_SERVING_MAXLEN", "1024"))
    d_model = int(os.environ.get("MXNET_HLO_SERVING_DMODEL", "256"))
    block = 16
    cfg = tf.TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=8, n_kv_heads=2,
        n_layers=2, d_ff=4 * d_model, max_len=max_len,
        kv_cache_int8=True)
    params = tf.init_params(cfg, seed=0)
    pool = tf.init_paged_cache(cfg, slots * (max_len // block) + 1,
                               block)
    tables = jnp.zeros((slots, max_len // block), jnp.int32)
    toks = jnp.zeros((slots,), jnp.int32)
    pos = jnp.zeros((slots,), jnp.int32)

    def lower(flag):
        if flag:
            os.environ["MXNET_PAGED_DECODE_PALLAS"] = "1"
        else:
            os.environ.pop("MXNET_PAGED_DECODE_PALLAS", None)
        fn = jax.jit(lambda p, pl, tb, t, ps:
                     tf.decode_step_paged(p, pl, tb, t, ps, cfg))
        c = fn.lower(params, pool, tables, toks, pos).compile()
        return hlo.parse_hlo(c.as_text())

    print("serving decode HLO: slots=%d max_len=%d d_model=%d "
          "int8_kv=on block=%d" % (slots, max_len, d_model, block))
    ga, gt = summarize("gather (flag off)", lower(False))
    ka, kt = summarize("kernel (flag on)", lower(True))
    os.environ.pop("MXNET_PAGED_DECODE_PALLAS", None)
    print("\n== diff (kernel - gather) ==")
    print("  total: %+.3f GB" % ((kt - gt) / 1e9))
    for op in sorted(set(ga) | set(ka),
                     key=lambda o: -(ka[o][0] - ga[o][0])):
        d = ka[op][0] - ga[op][0]
        if abs(d) < 1e6:
            continue
        print("  %-24s %+8.3f GB  (x%d vs x%d)" % (
            op, d / 1e9, ka[op][1], ga[op][1]))


if __name__ == "__main__":
    if "serving" in sys.argv[1:]:
        serving()
    else:
        main()
