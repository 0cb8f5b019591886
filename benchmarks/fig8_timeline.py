"""Fig. 8 reproduction: per-stage execution timeline of the pipeline.

The paper profiles each OpenCL kernel (MemRD / Conv / Pool / LRN / MemWR)
over an AlexNet/VGG run. Our stages are the fused groups from
models.cnn.fuse_plan; we time each group's jitted computation and report
the share of total runtime — the same breakdown the paper's timeline shows
(conv dominating, LRN a thin slice, pooling nearly free inside the fused
groups).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.cnn import fuse_plan, init_cnn_params, run_group


def stage_times(name: str, batch: int = 1, repeats: int = 2):
    cfg = get_config(name)
    key = jax.random.key(0)
    params = init_cnn_params(key, cfg)
    x = jax.random.normal(key, (batch, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    rows = []
    for group in fuse_plan(cfg):
        label = "+".join(cfg.layers[i].kind for i in group)
        fn = jax.jit(lambda p, v, g=group: run_group(p, v, cfg, g))
        args = (params, x)

        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn(*args))
        dt = (time.perf_counter() - t0) / repeats
        rows.append({"stage": label, "ms": dt * 1e3})
        x = out                                     # feed forward
    total = sum(r["ms"] for r in rows)
    for r in rows:
        r["share"] = r["ms"] / total
    return rows, total


def main(csv=False):
    for name in ("alexnet", "vgg16"):
        rows, total = stage_times(name)
        print(f"\n=== Fig.8 stage timeline ({name}, batch=1, CPU) ===")
        for r in rows:
            bar = "#" * int(r["share"] * 40)
            print(f"{r['stage']:12s} {r['ms']:9.2f} ms {r['share']:6.1%} {bar}")
        print(f"{'total':12s} {total:9.2f} ms")
        if csv:
            print(f"fig8_timeline_{name},{total*1e3:.0f},n_stages={len(rows)}")


if __name__ == "__main__":
    main()
