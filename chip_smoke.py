#!/usr/bin/env python3
"""On-chip smoke test of the compile-once AlexNet serving path.

Drives ``compile_cnn(get_config("alexnet"), spec)`` -> ``CompiledCNN
.forward`` / ``.serve`` once at AlexNet's full published widths (227x227x3
input, 96..4096 channels, 1000 classes) with random weights from
``--seed``, in fp32 and in int8, with the Pallas kernels compiled by
Mosaic. Each phase prints one line; any failure exits non-zero before
the last line, which is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip, the default):
  device  platform, device kind, count, jax and libtpu versions; exits at
          once unless the platform is ``tpu``.
  fp32    compile, count the ``tpu_custom_call`` kernels in the forward,
          compare logits with the XLA reference (``use_pallas=False`` at
          HIGHEST matmul precision), serve ~20 seeded requests.
  int8    the same with ``Precision(quant="int8")``, compared with the
          exact-int32 reference path (``repro.quant.ref``).

``--chips 4`` runs only the placements that exist across chips: dp over
four replicas and a four-stage GPipe pipeline, each compared on logits
with a single-chip forward of the same batch.

Usage: ``python3 chip_smoke.py [--seed N] [--chips 1|4]``. The persistent
compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``; a second run reports the cache hits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BATCH = 8
N_REQUESTS = 20
# Logit tolerances, stated before any run. The Pallas path replaces exact
# LRN with the paper's piecewise-linear LRN (<= 0.5 % per element); at
# full AlexNet width on random weights that moves the logits by ~0.75 %
# relative L2 and ~0.02 absolute (XLA reference with the PWL LRN swapped
# in, on a CPU). The bounds leave about 3x headroom; int8 adds requantize
# flips where the PWL LRN output crosses a rounding boundary.
TOL = {"fp32": {"rel_l2": 2e-2, "max_abs": 0.1},
       "int8": {"rel_l2": 5e-2, "max_abs": 0.25},
       # placements run the same kernels; only summation order may move
       "placement": {"rel_l2": 1e-4, "max_abs": 1e-3}}


def _fail(msg: str) -> "SystemExit":
    print(f"[chip_smoke] FAIL {msg}", file=sys.stderr, flush=True)
    return SystemExit(1)


def _say(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase}: " + json.dumps(fields, sort_keys=True),
          flush=True)


def _errors(got, want) -> dict:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise _fail(f"logits shape {got.shape} vs {want.shape}, finite="
                    f"{bool(np.isfinite(got).all())}")
    return {"max_abs": float(np.abs(got - want).max()),
            "rel_l2": float(np.linalg.norm(got - want)
                            / np.linalg.norm(want))}


def _check(phase: str, err: dict, tol: dict) -> None:
    bad = {k: (err[k], tol[k]) for k in tol if not err[k] <= tol[k]}
    if bad:
        raise _fail(f"{phase}: logit error over tolerance {bad}")


class _CacheEvents:
    """Counts JAX's persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"cache_hits": self.hits, "cache_misses": self.misses}
        self.hits = self.misses = 0
        return out


def device_phase(want_count: int) -> dict:
    import importlib.metadata

    import jax

    from repro.core.roofline import device_peaks
    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    _say("device", jax=jax.__version__, libtpu=libtpu, **info)
    if d.platform != "tpu":
        raise _fail(f"needs a TPU, JAX found platform {d.platform!r}")
    if len(devs) < want_count:
        raise _fail(f"needs {want_count} chips, JAX found {len(devs)}")
    device_peaks(d.device_kind)          # a kind with no peaks is an error
    return info


def _batch(cfg, seed: int, n: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(
        (n, cfg.input_hw, cfg.input_hw, cfg.input_ch)).astype(np.float32))


def _kernel_groups(cfg) -> int:
    """Fusion groups that run as one Pallas kernel: conv(+pool), LRN, FC."""
    from repro.models.cnn import fuse_plan
    return sum(cfg.layers[g[0]].kind in ("conv", "lrn", "fc")
               for g in fuse_plan(cfg))


def precision_phase(name: str, seed: int, cache: _CacheEvents) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve_cnn import synthetic_requests
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import ExecutionSpec, Precision, Serving, compile_cnn

    cfg = get_config("alexnet")
    params = init_cnn_params(jax.random.key(seed), cfg)
    precision = Precision(quant="int8" if name == "int8" else "none")
    src = (params, _batch(cfg, seed + 1, BATCH)) if name == "int8" else params
    x = _batch(cfg, seed + 2, BATCH)

    t0 = time.perf_counter()
    compiled = compile_cnn(cfg, ExecutionSpec(
        precision=precision, serving=Serving(batch=BATCH)), src)
    t_plan = time.perf_counter() - t0
    # each compiled Pallas kernel is one custom call in the lowered forward
    n_kernels = compiled.lower(x).as_text().count("@tpu_custom_call(")
    t0 = time.perf_counter()
    logits = jax.block_until_ready(compiled.forward(x))
    t_first = time.perf_counter() - t0
    compile_info = cache.take()
    t0 = time.perf_counter()
    jax.block_until_ready(compiled.forward(x))
    t_warm = time.perf_counter() - t0

    # the reference runs the same (for int8: the same calibrated) params
    ref = compile_cnn(cfg, ExecutionSpec(
        precision=precision, serving=Serving(batch=BATCH),
        use_pallas=False), compiled.params)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(x)
    err = _errors(logits, want)
    need = _kernel_groups(cfg)
    _say(name, plan_s=t_plan, first_forward_s=t_first,
         warm_forward_s=t_warm, tpu_custom_calls=n_kernels,
         kernel_groups=need, logits_shape=list(np.shape(logits)),
         tol=TOL[name], **compile_info, **err)
    if n_kernels < need:
        raise _fail(f"{name}: {n_kernels} tpu_custom_call(s) in the "
                    f"forward, need >= {need} (one per conv group, LRN, "
                    f"FC) — kernels did not compile for the chip")
    _check(name, err, TOL[name])

    reqs = synthetic_requests(N_REQUESTS, cfg.input_hw, cfg.input_ch,
                              rate=200.0, seed=seed)
    rep = compiled.serve(reqs)
    _say(f"{name}-serve", completions=len(rep.completions),
         n_done=rep.n_done, throughput_img_s=rep.throughput,
         p50_ms=rep.p50_ms, p95_ms=rep.p95_ms, clock=rep.clock)
    if rep.n_done != N_REQUESTS:
        raise _fail(f"{name}: served {rep.n_done}/{N_REQUESTS} requests")


def _placement(out) -> list:
    """Where each shard of ``out`` landed: ``[device, index]`` pairs."""
    return sorted([str(s.device), str(s.index)]
                  for s in out.addressable_shards)


def chips4_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import (ExecutionSpec, Placement, Serving,
                                compile_cnn)

    cfg = get_config("alexnet")
    params = init_cnn_params(jax.random.key(seed), cfg)
    x = _batch(cfg, seed + 2, BATCH)
    single = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=BATCH)),
                         params)
    want = jax.block_until_ready(single.forward(x))
    _say("single", devices=_placement(want))
    for name, placement in (("dp4", Placement(replicas=4)),
                            ("pp4", Placement(pp_stages=4))):
        c = compile_cnn(cfg, ExecutionSpec(
            placement=placement, serving=Serving(batch=BATCH)), params)
        got = jax.block_until_ready(c.forward(x))
        err = _errors(got, want)
        _say(name, mesh_axes=list(c.mesh.axis_names),
             mesh_devices=[[str(d) for d in row] for row in c.mesh.devices],
             logits_shards=_placement(got), tol=TOL["placement"], **err)
        _check(name, err, TOL["placement"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, calibration and requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the dp4/pp4 placements vs one chip")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        raise _fail(f"the repro package is not next to this script "
                    f"({os.path.join(ROOT, 'src')}): {e}")
    cache_dir = enable_compile_cache()
    cache = _CacheEvents()
    info = device_phase(args.chips)
    _say("cache", dir=cache_dir)
    if args.chips == 4:
        chips4_phase(args.seed)
    else:
        precision_phase("fp32", args.seed, cache)
        precision_phase("int8", args.seed, cache)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
