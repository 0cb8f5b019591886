"""The paper's CORE claim, quantified: pipelined-kernel fusion cuts global
memory traffic vs (a) unfused stage-per-kernel execution and (b) the
im2col+GEMM organization of FPGA'16 [4].

Two measurements:
  * analytic — the traffic model in core/pipeline.py (per stage);
  * compiled — XLA 'bytes accessed' for the fused vs unfused jitted forward
    at smoke scale (the compiler-level counterpart; fusion here = XLA
    op fusion + our conv+pool kernel grouping).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.pipeline import (bandwidth_model, fusion_savings,
                                 im2col_gemm_traffic, measure_traffic)
from repro.models.cnn import (cnn_forward_stage, fuse_plan,
                              init_cnn_params, run_group)


def main(csv=False):
    print("\n=== Bandwidth: PipeCNN fusion vs alternatives (analytic, "
          "full scale, fp32, per image) ===")
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name)
        unf, fus, red = fusion_savings(cfg)
        gemm = im2col_gemm_traffic(cfg)
        # PipeCNN's batched-FC mode: weights amortize over the batch
        unf16 = sum(s.total
                    for s in bandwidth_model(cfg, batch=16, fused=False)) / 16
        fus16 = sum(s.total
                    for s in bandwidth_model(cfg, batch=16, fused=True)) / 16
        print(f"{name:8s}: im2col-GEMM[4] {gemm/1e6:8.1f} MB | "
              f"unfused {unf/1e6:8.1f} MB | fused(PipeCNN) {fus/1e6:8.1f} MB"
              f" | vs[4] -{1-fus/gemm:.1%}")
        print(f"{'':8s}  batch=16 (batched-FC weight reuse): "
              f"unfused {unf16/1e6:8.1f} MB | fused {fus16/1e6:8.1f} MB "
              f"per image | total vs [4] -{1-fus16/gemm:.1%}")
        if csv:
            print(f"bandwidth_{name},0,fused_vs_gemm_saving="
                  f"{(1-fus/gemm)*100:.1f}")

    print("\n--- compiled bytes-accessed (smoke scale, XLA) ---")
    print("(unfused = one jit PER STAGE, the separate-OpenCL-kernel "
          "organization; fused = whole-net jit)")
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name).smoke()
        key = jax.random.key(0)
        params = init_cnn_params(key, cfg)
        x = jax.random.normal(key, (1, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        fused_b = measure_traffic(
            lambda p, v: cnn_forward_stage(p, v, cfg, fuse_plan(cfg)),
            params, x)
        # unfused: separate compilation per layer (singleton groups) =>
        # forced HBM round trips
        unfused_b = 0.0
        h = x
        for i in range(len(cfg.layers)):
            fn = lambda p, v, g=(i,): run_group(p, v, cfg, g)
            unfused_b += measure_traffic(fn, params, h)
            h = fn(params, h)
        print(f"{name:8s}: fused {fused_b/1e6:7.1f} MB "
              f"unfused {unfused_b/1e6:7.1f} MB "
              f"(fusion saves {1-fused_b/max(unfused_b,1):.1%})")


if __name__ == "__main__":
    main()
