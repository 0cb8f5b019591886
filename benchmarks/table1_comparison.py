"""Table I reproduction: classification time / throughput / efficiency for
AlexNet and VGG-16, compared against the paper's FPGA numbers.

Two result columns per model:
  * CPU-measured  — this container's wall clock for the full-scale forward
    (XLA path; the Pallas path is validated separately, interpret mode is
    not a performance vehicle);
  * v5e-projected — analytic roofline projection of the fused pipeline on
    one TPU v5e chip (the hardware this system targets), the analogue of
    the paper's 33.9 GOPS on Stratix-V.

Paper reference points (Table I): AlexNet 43 ms / 33.9 GOPS / 162 DSP /
0.21 GOPS/DSP @ fp32; VGG-16 718 ms.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.config import flops_per_image
from repro.core.pipeline import fusion_savings
from repro.core.roofline import HBM_BW, PEAK_FLOPS
from repro.models.cnn import init_cnn_params
from repro.pipeline import ExecutionSpec, Serving, compile_cnn

PAPER = {
    "alexnet": {"ms": 43.0, "gops": 33.9},
    "vgg16": {"ms": 718.0, "gops": None},   # paper reports time only
}


def bench_model(name: str, batch: int = 1, repeats: int = 2):
    cfg = get_config(name)
    key = jax.random.key(0)
    params = init_cnn_params(key, cfg)
    x = jax.random.normal(key, (batch, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    fwd = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=batch),
                                         use_pallas=False), params,
                      with_engine=False).forward
    fwd(x).block_until_ready()                      # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        fwd(x).block_until_ready()
    cpu_s = (time.perf_counter() - t0) / repeats / batch

    ops = flops_per_image(cfg)
    cpu_gops = ops / cpu_s / 1e9

    # v5e single-chip projection: fused pipeline => max(compute, memory)
    _, fused_bytes, _ = fusion_savings(cfg, batch=1)
    t_comp = ops / PEAK_FLOPS
    t_mem = fused_bytes / HBM_BW
    v5e_s = max(t_comp, t_mem)
    v5e_gops = ops / v5e_s / 1e9
    bound = "compute" if t_comp >= t_mem else "memory"

    return {
        "model": name, "gop_per_image": ops / 1e9,
        "cpu_ms": cpu_s * 1e3, "cpu_gops": cpu_gops,
        "v5e_ms": v5e_s * 1e3, "v5e_gops": v5e_gops, "v5e_bound": bound,
        "paper_ms": PAPER[name]["ms"], "paper_gops": PAPER[name]["gops"],
    }


def main(csv=False):
    rows = [bench_model("alexnet"), bench_model("vgg16")]
    print("\n=== Table I reproduction "
          "(paper: Stratix-V A7; ours: CPU measured + v5e projected) ===")
    hdr = (f"{'model':10s} {'GOP/img':>8s} {'paper ms':>9s} {'cpu ms':>9s} "
           f"{'cpu GOPS':>9s} {'v5e ms':>8s} {'v5e GOPS':>9s} {'bound':>8s}")
    print(hdr)
    for r in rows:
        print(f"{r['model']:10s} {r['gop_per_image']:8.2f} "
              f"{r['paper_ms']:9.1f} {r['cpu_ms']:9.1f} "
              f"{r['cpu_gops']:9.2f} {r['v5e_ms']:8.2f} "
              f"{r['v5e_gops']:9.1f} {r['v5e_bound']:>8s}")
    if csv:
        for r in rows:
            print(f"table1_{r['model']},{r['cpu_ms']*1e3:.0f},"
                  f"v5e_gops={r['v5e_gops']:.1f}")
    return rows


if __name__ == "__main__":
    main()
