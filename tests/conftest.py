"""Shared fixtures. NOTE: no XLA_FLAGS device-count override here — smoke
tests and benches must see the real single device; only launch/dryrun.py
requests 512 placeholder devices (per the deliverable spec)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)


@pytest.fixture(scope="session")
def forward():
    """``forward(params, x, cfg, use_pallas=False)``: the logits of
    ``compile_cnn(...).forward`` compiled at ``x``'s batch — the int8
    pipeline for ``QuantizedCNNParams``, the float one otherwise."""
    from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                compile_cnn)
    from repro.quant import QuantizedCNNParams

    def run(params, x, cfg, use_pallas=False):
        quant = isinstance(params, QuantizedCNNParams)
        spec = ExecutionSpec(
            precision=Precision(quant="int8" if quant else "none"),
            serving=Serving(batch=x.shape[0]), use_pallas=use_pallas)
        return compile_cnn(cfg, spec, params, with_engine=False).forward(x)
    return run
