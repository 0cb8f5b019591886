"""Quantization accuracy harness: int8 pipeline vs fp32, per layer and
end to end (the paper's Table-1 precision column, measured).

PipeCNN reports that fixed-point inference costs ~1% accuracy for a 34%
DSP saving; this harness quantifies the repro's analogous trade on a
synthetic calibration/eval set:

  * per-layer output error — relative L2 between the dequantized int8
    activation and the fp32 activation at every pipeline-stage boundary
    (shows where quantization error enters and how it propagates);
  * end-to-end argmax agreement — fraction of images whose int8 top-1
    class matches fp32, on the calibration set itself (the acceptance
    metric: >= 99%) and on a held-out set of the same distribution;
  * a fake-quant cross-check — the fp32-math fake-quant forward of the
    first conv layer vs the exact-int path, separating calibration error
    (shared) from integer-kernel error (~0 by construction).

Run: PYTHONPATH=src python -m benchmarks.quant_accuracy [--smoke]
         [--arch alexnet vgg16] [--calib 8] [--eval 96] [--pallas]

``--smoke`` shrinks the models (CPU CI) and ASSERTS the >= 99% agreement
acceptance bound, exiting non-zero on failure.
"""
from __future__ import annotations

import argparse
import sys

AGREEMENT_BOUND = 0.99          # acceptance: int8 argmax agreement vs fp32


def run_arch(name: str, *, smoke: bool, n_calib: int, n_eval: int,
             use_pallas: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models.cnn import fuse_plan, init_cnn_params, run_group
    from repro.pipeline import ExecutionSpec, Precision, Serving, compile_cnn
    from repro.quant import (calibrate_cnn, dequantize, group_forward_ref,
                             quantize)
    from repro.quant.ref import conv_fake_quant_ref

    cfg = get_config(name)
    if smoke:
        cfg = cfg.smoke()
    key = jax.random.key(0)
    params = init_cnn_params(key, cfg)
    hw, ch = cfg.input_hw, cfg.input_ch
    rng = np.random.default_rng(123)
    calib = jnp.asarray(rng.standard_normal((n_calib, hw, hw, ch))
                        .astype(np.float32))
    held = jnp.asarray(rng.standard_normal((n_eval, hw, hw, ch))
                       .astype(np.float32))

    qp = calibrate_cnn(params, calib, cfg)

    def compiled(p, batch):
        quant = "none" if p is params else "int8"
        return compile_cnn(cfg, ExecutionSpec(
            precision=Precision(quant=quant), serving=Serving(batch=batch),
            use_pallas=use_pallas), p, with_engine=False)

    # -- per-layer output error on the calibration batch ------------------
    # (the final group's activations double as the logits for the calib
    # agreement below — no recomputation of either forward)
    fp_acts = {g: a for g, a in group_forward_ref(params, calib, cfg)}
    layer_err = {}
    first_q = None
    plans = compiled(qp, n_calib).group_plans
    q, s = quantize(calib, qp.in_scale), qp.in_scale
    for g in fuse_plan(cfg):
        q = run_group(qp, q, cfg, g, plans=plans, use_pallas=use_pallas)
        if cfg.layers[g[0]].kind != "pool":   # pool passes the scale on
            s = qp.layers[g[0]].y_scale
        got = dequantize(q, s) if s is not None else q
        want = fp_acts[g]
        err = float(jnp.linalg.norm(got - want)
                    / jnp.maximum(jnp.linalg.norm(want), 1e-12))
        kinds = "+".join(cfg.layers[i].kind for i in g)
        layer_err[f"{kinds}@{g[0]}"] = err
        if first_q is None:
            first_q = (q, s)
    logits_q = q

    # -- end-to-end argmax agreement --------------------------------------
    def agreement(y_fp, y_q):
        return float(jnp.mean(jnp.argmax(y_fp, -1) == jnp.argmax(y_q, -1)))

    logits_fp = next(reversed(fp_acts.values()))
    agree_calib = agreement(logits_fp, logits_q)
    agree_held = agreement(compiled(params, n_eval).forward(held),
                           compiled(qp, n_eval).forward(held))

    # -- fake-quant cross-check on the first conv group -------------------
    # fp32 math on fake-quantized operands vs the exact-int path, both
    # requantized by the same y_scale: any difference is float-accumulation
    # rounding flipping a borderline code, so it is reported in CODES
    # (steps of y_scale) and should be <= 1.
    g0 = next(iter(fp_acts))
    fq_codes = None
    if len(g0) == 1 and cfg.layers[g0[0]].kind == "conv":
        l0, ql0, p0 = cfg.layers[g0[0]], qp.layers[g0[0]], params[g0[0]]
        fq = conv_fake_quant_ref(
            calib, p0["w"], p0["b"], x_scale=qp.in_scale,
            w_scale=ql0.w_scale, stride=l0.stride, pad=l0.pad,
            relu=l0.relu, groups=l0.groups, out_scale=ql0.y_scale)
        q0, s0 = first_q                  # captured from the loop above
        got = dequantize(q0, s0) if s0 is not None else q0
        fq_codes = float(jnp.max(jnp.abs(got - fq)) / (s0 or 1.0))

    return {"arch": cfg.name, "layer_err": layer_err,
            "argmax_agreement_calib": agree_calib,
            "argmax_agreement_heldout": agree_held,
            "fake_quant_vs_int_codes": fq_codes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrunk models + assert the >=99%% agreement bound")
    ap.add_argument("--arch", nargs="+", default=["alexnet", "vgg16"])
    ap.add_argument("--calib", type=int, default=8,
                    help="calibration images")
    ap.add_argument("--eval", type=int, default=96, dest="n_eval",
                    help="held-out eval images")
    ap.add_argument("--pallas", action="store_true",
                    help="run the int8 Pallas kernels (default: the exact "
                         "int32 XLA reference — same integer math)")
    args = ap.parse_args(argv)

    sys.path.insert(0, "src")
    failures = []
    for name in args.arch:
        r = run_arch(name, smoke=args.smoke, n_calib=args.calib,
                     n_eval=args.n_eval, use_pallas=args.pallas)
        print(f"[quant_accuracy] {r['arch']}"
              f"{' (smoke)' if args.smoke else ''}: argmax agreement "
              f"{r['argmax_agreement_calib']:.1%} (calib, n={args.calib}) "
              f"/ {r['argmax_agreement_heldout']:.1%} "
              f"(held-out, n={args.n_eval})")
        for lname, err in r["layer_err"].items():
            print(f"  layer {lname:<14s} rel_l2 {err:.4f}")
        if r["fake_quant_vs_int_codes"] is not None:
            print(f"  fake-quant vs exact-int (conv1) max|diff| "
                  f"{r['fake_quant_vs_int_codes']:.3g} codes")
        if r["argmax_agreement_calib"] < AGREEMENT_BOUND:
            failures.append(
                f"{r['arch']}: calib agreement "
                f"{r['argmax_agreement_calib']:.1%} < "
                f"{AGREEMENT_BOUND:.0%}")
    if failures and args.smoke:
        print("[quant_accuracy] FAIL: " + "; ".join(failures))
        return 1
    print("[quant_accuracy] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
