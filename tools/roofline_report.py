#!/usr/bin/env python
"""roofline_report: static per-op MXU lane/roofline table for a model.

Answers "what MFU CAN this model reach on the 128-wide MXU" from the HLO
alone — no device, no timing, deterministic on CPU. The docs/perf.md
roofline table (ResNet-56's three stages fill 16/32/64 of 128 output
lanes -> a ~29% flop-weighted MFU ceiling) is REGENERATED by this tool
instead of hand arithmetic:

    python tools/roofline_report.py                      # flagship fwd
    python tools/roofline_report.py --what train         # fwd + bwd step
    python tools/roofline_report.py --what round         # whole round program
    python tools/roofline_report.py --markdown           # perf.md table

``--what fwd`` lowers the eval forward (the same program bench.py costs
with XLA's cost model); ``--what train`` lowers one grad step (adds the
dgrad/wgrad GEMM shapes); ``--what round`` builds the REAL round program
through FedAvgAPI + the obs/compile.timed_build attribution hook (packed
schedule, tiny synthetic data — conv shapes depend on model/batch, not on
records) and reports what a traced run would record. Output: the per-op
GEMM table (top-k by executed FLOPs), the per-output-channel stage table,
flop-weighted output/reduction lane ceilings, and — when a TPU peak is
known for the current device — the MFU ceiling those lanes impose.

Exit codes: 0 ok; 2 when the model cannot be lowered/analyzed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _analyze_fwd(model: str, batch: int, dtype_name: str, top_k: int):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import create_model
    from fedml_tpu.obs import cost

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    bundle = create_model(model, 10, dtype=dtype, input_shape=(32, 32, 3),
                          bn_impl=os.environ.get("BENCH_BN", "xla"),
                          conv_impl=os.environ.get("BENCH_CONV", "xla"))
    variables = bundle.init(jax.random.PRNGKey(0), 2)
    x = jnp.zeros((batch,) + tuple(bundle.input_shape), dtype)

    def fwd(v, xx):
        return bundle.apply_eval(v, xx)

    return cost.analyze_lowered(jax.jit(fwd).lower(variables, x), top_k=top_k)


def _analyze_train(model: str, batch: int, dtype_name: str, top_k: int):
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.models import create_model
    from fedml_tpu.obs import cost

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    bundle = create_model(model, 10, dtype=dtype, input_shape=(32, 32, 3),
                          bn_impl=os.environ.get("BENCH_BN", "xla"),
                          conv_impl=os.environ.get("BENCH_CONV", "xla"))
    variables = bundle.init(jax.random.PRNGKey(0), 2)
    x = jnp.zeros((batch,) + tuple(bundle.input_shape), dtype)
    y = jnp.zeros((batch,), jnp.int32)

    def loss_fn(v, xx, yy):
        logits, _ = bundle.apply_train(v, xx, jax.random.PRNGKey(1))
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), yy))

    return cost.analyze_lowered(
        jax.jit(jax.grad(loss_fn)).lower(variables, x, y), top_k=top_k)


def _analyze_round(model: str, batch: int, dtype_name: str, top_k: int):
    """The REAL round program: a tiny flagship-shaped FedAvgAPI run with
    cost attribution on; returns the attribution record timed_build
    captured for the program the round actually executed."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model
    from fedml_tpu.obs import cost

    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    ds = make_synthetic_classification(
        "roofline", (32, 32, 3), 10, 4, records_per_client=2 * batch,
        partition_method="homo", partition_alpha=0.5, batch_size=batch,
        seed=0)
    cfg = FedConfig(model=model, dataset="cifar10", client_num_in_total=4,
                    client_num_per_round=4, comm_round=1, batch_size=batch,
                    epochs=1, lr=0.1, dtype=dtype_name,
                    frequency_of_the_test=10_000, seed=0,
                    pack_lanes=int(os.environ.get("BENCH_PACK_LANES", "2")),
                    device_data="on", cost_attribution=True)
    bundle = create_model(model, 10, dtype=dtype,
                          input_shape=ds.train_x.shape[2:],
                          bn_impl=os.environ.get("BENCH_BN", "xla"),
                          conv_impl=os.environ.get("BENCH_CONV", "xla"))
    cost.reset_cost_tables()
    cost.configure_from(cfg)
    try:
        api = FedAvgAPI(ds, cfg, bundle)
        api.run_round(1)
    finally:
        cost.enable_cost_attribution(False)
    tables = cost.cost_tables()
    if not tables:
        return None
    # a round usually builds one program, but a mixed path (bucketed gather
    # + packed, say) can attribute several — report the FLOP-dominant one
    # and say so, rather than whichever happened to be attributed first
    rec = max(tables.values(),
              key=lambda r: r["summary"]["gemm_flops_per_invocation"])
    if len(tables) > 1:
        print(f"note: round attributed {sorted(tables)}; reporting the "
              f"FLOP-dominant {rec['program']!r}", file=sys.stderr)
    return {"ops": rec["ops"], "summary": rec["summary"],
            "xla_cost": rec["xla_cost"], "program": rec["program"],
            "shape_key": rec["shape_key"]}


def format_report(rep: dict, model: str, what: str, peak, peak_entry) -> str:
    s = rep["summary"]
    lines = [f"roofline: {model} ({what} program), "
             f"{s['gemm_ops']} GEMM op(s), "
             f"{s['gemm_flops_per_invocation'] / 1e9:.3f} GFLOP/invocation"
             + (" [loop trip count unknown for some ops]"
                if s["unknown_trip_counts"] else "")]
    if rep.get("xla_cost"):
        lines.append(
            f"XLA cost model (loop bodies once): "
            f"{rep['xla_cost']['flops'] / 1e9:.3f} GFLOP, "
            f"{rep['xla_cost']['bytes_accessed'] / 1e6:.1f} MB accessed")
    lines.append("")
    lines.append("per-output-channel stage table (MXU output-lane fill):")
    lines.append("  N (Cout/group)  lane fill   FLOP share")
    for n, row in s["by_output_channels"].items():
        lines.append(f"  {n:>14}  {row['out_lane_fill'] * 100:>8.1f}%  "
                     f"{row['flops_frac'] * 100:>9.1f}%")
    lines.append("")
    lines.append(
        f"flop-weighted output-lane ceiling: "
        f"{s['out_lane_ceiling'] * 100:.1f}% "
        f"(reduction-lane: {s['red_lane_ceiling'] * 100:.1f}%)")
    if peak:
        lines.append(
            f"=> MFU ceiling on {peak_entry} ({peak / 1e12:.0f} TFLOP/s "
            f"bf16 peak): {s['out_lane_ceiling'] * 100:.1f}% — no schedule "
            "can exceed it while these GEMM shapes leave lanes empty")
    lines.append("")
    lines.append(f"top ops by executed FLOPs:")
    lines.append("  kind  count        M      K      N  groups   fill"
                 "  GFLOPs  FLOP/byte")
    for o in s["top_ops"]:
        lines.append(
            f"  {o['kind']:<5} {o['count']:>5} {o['m']:>8} {o['k']:>6} "
            f"{o['n']:>6} {o['groups']:>7} "
            f"{o['out_lane_fill'] * 100:>5.1f}% "
            f"{o['flops'] * o['count'] / 1e9:>7.3f}  {o['intensity']:>9.1f}")
    return "\n".join(lines)


def format_markdown(rep: dict) -> str:
    """The docs/perf.md roofline table, regenerated."""
    s = rep["summary"]
    lines = ["| N = Cout/group | MXU output-lane fill | share of GEMM FLOPs |",
             "|---|---|---|"]
    for n, row in s["by_output_channels"].items():
        if row["flops_frac"] < 0.005:
            continue   # the classifier head etc. — noise rows
        lines.append(f"| {n} | {min(int(n), 128)}/128 = "
                     f"{row['out_lane_fill'] * 100:.1f}% | "
                     f"{row['flops_frac'] * 100:.1f}% |")
    lines.append("")
    lines.append(f"Flop-weighted output-lane ceiling: "
                 f"**{s['out_lane_ceiling'] * 100:.1f}%**")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default=os.environ.get("BENCH_MODEL",
                                                      "resnet56"))
    ap.add_argument("--what", choices=("fwd", "train", "round"),
                    default="fwd")
    ap.add_argument("--batch", type=int,
                    default=int(os.environ.get("BENCH_BATCH", "64")))
    ap.add_argument("--dtype", choices=("bfloat16", "float32"),
                    default="bfloat16")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--markdown", action="store_true",
                    help="emit the docs/perf.md stage table and exit")
    args = ap.parse_args(argv)

    import jax

    from fedml_tpu.obs import cost

    fn = {"fwd": _analyze_fwd, "train": _analyze_train,
          "round": _analyze_round}[args.what]
    rep = fn(args.model, args.batch, args.dtype, args.top)
    if rep is None or not rep["summary"]["gemm_ops"]:
        print(f"no GEMM ops attributed for {args.model} ({args.what})",
              file=sys.stderr)
        return 2
    peak, peak_entry = cost.peak_flops(jax.devices()[0])
    if args.markdown:
        print(format_markdown(rep))
    elif args.json:
        print(json.dumps({"model": args.model, "what": args.what,
                          "summary": rep["summary"],
                          "xla_cost": rep.get("xla_cost"),
                          "peak_bf16_flops": peak,
                          "peak_table_entry": peak_entry}, indent=2))
    else:
        print(format_report(rep, args.model, args.what, peak, peak_entry))
    return 0


if __name__ == "__main__":
    sys.exit(main())
