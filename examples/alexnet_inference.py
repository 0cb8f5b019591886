"""The paper's own scenario: batched AlexNet image classification through
the PipeCNN pipeline, with the VEC_SIZE/CU_NUM knobs exposed.

Mirrors the paper's measurement: ms/image at batch 16 (their Fig. 8 batch),
plus the fused-vs-unfused bandwidth model for this exact run.

Run:  PYTHONPATH=src python examples/alexnet_inference.py [--full]
      (--full uses the real 227x227x(96..384ch) network on CPU: slower)
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.config import flops_per_image
from repro.core.pipeline import fusion_savings
from repro.data.pipeline import image_batches
from repro.models.cnn import init_cnn_params
from repro.pipeline import ExecutionSpec, Serving, compile_cnn

ap = argparse.ArgumentParser()
ap.add_argument("--full", action="store_true")
ap.add_argument("--batch", type=int, default=16)
args = ap.parse_args()

cfg = get_config("alexnet") if args.full else get_config("alexnet").smoke()
key = jax.random.key(0)
params = init_cnn_params(key, cfg)
stream = image_batches(args.batch, cfg.input_hw, cfg.input_ch, 1000)

fwd = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=args.batch),
                                     use_pallas=False), params,
                  with_engine=False).forward
batch = next(stream)
x = jnp.asarray(batch["images"])
fwd(x).block_until_ready()                            # compile

t0 = time.perf_counter()
n = 3
for _ in range(n):
    batch = next(stream)
    preds = jnp.argmax(fwd(jnp.asarray(batch["images"])), -1)
    preds.block_until_ready()
dt = (time.perf_counter() - t0) / (n * args.batch)

ops = flops_per_image(cfg)
unf, fus, red = fusion_savings(cfg, batch=args.batch)
print(f"AlexNet ({'full' if args.full else 'smoke'}): "
      f"{dt*1e3:.2f} ms/image on CPU "
      f"({ops/dt/1e9:.2f} GOPS; paper on Stratix-V: 43 ms, 33.9 GOPS)")
print(f"pipeline traffic at batch {args.batch}: fused {fus/1e6:.1f} MB vs "
      f"unfused {unf/1e6:.1f} MB ({red:.1%} saved)")
print(f"DSE knobs in use: VEC_SIZE={cfg.vec_size} CU_NUM={cfg.cu_num} "
      f"(c_blk/m_blk of the fused kernel)")
