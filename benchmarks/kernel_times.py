"""Device time of each conv kernel call in one compiled forward, on a TPU.

Compiles ``--arch`` at ``--batch`` through ``compile_cnn`` (float32 at
HIGHEST, one chip, random weights), runs ``--iters`` forwards under the
JAX profiler with the host and Python tracers off, and sums the device
durations of the trace's ``XLA Ops`` events by HLO instruction. Prints
one JSON line: milliseconds a forward per op family (``fused_conv``,
``pad``, ``copy``, ``fc``, ``lrn``) and, per ``fused_conv``, ``pad`` and
``copy`` instruction in the order the forward runs them, its output
shape and milliseconds a forward. The benchmark's trace sums a family
only; this splits it by conv group and by the glue around each:

    PYTHONPATH=src python benchmarks/kernel_times.py --arch vgg16 --batch 32
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile

FAMILIES = ("fused_conv", "pad", "copy", "fc", "lrn")
LISTED = ("fused_conv", "pad", "copy")      # split by instruction


def kernel_times(path: str, iters: int) -> dict:
    """Per-family device ms a forward, and per instruction of the
    :data:`LISTED` families, from the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    total, first, shape = {}, {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                head, _, rest = ev.name.partition(" = ")
                head = head.lstrip("%").strip()
                if head.split(".")[0] not in FAMILIES:
                    continue
                total[head] = total.get(head, 0) + ev.duration_ns
                first.setdefault(head, ev.start_ns)
                shape.setdefault(head, rest.split(" ", 1)[0])
    if not total:
        raise SystemExit("no TPU op events in the trace: run on a TPU")
    family_ms = {}
    for head, ns in total.items():
        fam = head.split(".")[0]
        family_ms[fam] = family_ms.get(fam, 0.0) + ns / iters * 1e-6
    listed = {fam: [[head, shape[head], total[head] / iters * 1e-6]
                    for head in sorted(total, key=first.get)
                    if head.split(".")[0] == fam]
              for fam in LISTED}
    return {"family_ms": family_ms, **listed}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="vgg16")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import (ExecutionSpec, Placement, Precision,
                                Serving, compile_cnn)

    cfg = get_config(args.arch)
    spec = ExecutionSpec(precision=Precision(dtype="float32"),
                         placement=Placement(replicas=1),
                         serving=Serving(batch=args.batch))
    compiled = compile_cnn(cfg, spec, init_cnn_params(jax.random.key(0), cfg),
                           with_engine=False)
    x = np.random.default_rng(0).standard_normal(
        (args.batch, cfg.input_hw, cfg.input_hw, cfg.input_ch),
        dtype=np.float32)
    for _ in range(3):                        # compile and warm up
        np.asarray(compiled.forward(x))
    log_dir = tempfile.mkdtemp(prefix="kernel-times-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for _ in range(args.iters):
        np.asarray(compiled.forward(x))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {"arch": args.arch, "batch": args.batch, "iters": args.iters,
           "device": jax.devices()[0].device_kind,
           **kernel_times(path, args.iters)}
    print(json.dumps(out, sort_keys=True))


if __name__ == "__main__":
    main()
