"""Quickstart: the two faces of the framework in ~60 seconds.

1. The paper's CNN pipeline through the COMPILE-ONCE API
   (``repro.pipeline``): one ``compile_cnn(cfg, spec, params)`` resolves
   precision, kernel plans and placement into a ``CompiledCNN``; the run
   phase is just ``.forward(x)`` / ``.serve(requests)``. The plan table
   round-trips through JSON, so a committed artifact skips the DSE sweep.
2. The LM framework: train a small qwen3-family model a few steps, then
   greedy-decode from it.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import autotune
from repro.models import lm
from repro.models.cnn import init_cnn_params
from repro.pipeline import ExecutionSpec, Serving, compile_cnn
from repro.train.steps import init_train_state, serve_decode, serve_prefill, \
    train_step

key = jax.random.key(0)

# ---------------------------------------------------------------- CNN side
print("== PipeCNN compile-once pipeline (AlexNet, reduced) ==")
acfg = get_config("alexnet").smoke()
aparams = init_cnn_params(key, acfg)
images = jax.random.normal(key, (4, acfg.input_hw, acfg.input_hw,
                                 acfg.input_ch), jnp.float32)

# COMPILE: the spec declares everything up front (fp32, autotuned tiling,
# single placement, batch 4); compile_cnn runs the whole DSE now
spec = ExecutionSpec(serving=Serving(batch=4))
compiled = compile_cnn(acfg, spec, aparams)
print(f"compiled: {compiled}")

# RUN: the pallas kernel pipeline vs the XLA reference path
logits_k = compiled.forward(images)
logits = compile_cnn(acfg, ExecutionSpec(serving=Serving(batch=4),
                                         use_pallas=False),
                     aparams, with_engine=False).forward(images)
print(f"logits {logits.shape}; pallas-vs-xla max diff "
      f"{float(jnp.max(jnp.abs(logits - logits_k))):.2e}")

# the frozen plan table is DATA: save it, wipe the process registry
# (standing in for a fresh process), reload — the compile is pure cache
# hits, zero DSE sweeps (the committed-artifact path)
with tempfile.NamedTemporaryFile(suffix=".json") as f:
    compiled.save_plan(f.name)
    autotune.clear_registry()
    autotune.reset_sweep_stats()
    recompiled = compile_cnn(acfg, spec, aparams, plan_path=f.name)
    st = autotune.sweep_stats()
print(f"recompile from saved plan table: "
      f"{st['conv_sweeps'] + st['gemm_sweeps']} DSE sweeps, "
      f"{st['conv_hits'] + st['gemm_hits']} cache hits")
assert st["conv_sweeps"] + st["gemm_sweeps"] == 0

# ----------------------------------------------------------------- LM side
print("\n== LM framework (qwen3 family, smoke scale) ==")
cfg = get_config("qwen3-8b").smoke()
state = init_train_state(key, cfg)
step = jax.jit(lambda s, b: train_step(s, b, cfg), donate_argnums=0)
for i in range(5):
    toks = jax.random.randint(jax.random.key(i), (4, 33), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    state, metrics = step(state, batch)
    print(f"step {i}: loss {float(metrics['loss']):.4f} "
          f"gnorm {float(metrics['grad_norm']):.3f}")

print("\n== greedy decode ==")
prompts = jax.random.randint(key, (2, 8), 0, cfg.vocab)
ids, _, cache = jax.jit(
    lambda p, b: serve_prefill(p, b, cfg, 32))(state.params,
                                               {"tokens": prompts})
out = [ids]
for _ in range(6):
    ids, _, cache = jax.jit(
        lambda p, t, c: serve_decode(p, t, c, cfg))(state.params, ids, cache)
    out.append(ids)
print("generated:", jnp.concatenate(out, axis=1)[0].tolist())
print("\nquickstart OK")
