"""Fixed-point (int8) inference subsystem tests.

Covers: the symmetric quantization core (also backing gradient
compression), int8 conv/FC kernel parity vs the EXACT int32 reference
(bit-equality in interpret mode), calibration determinism, dtype-aware
autotuning (plan cache keyed by dtype, int8 picking cheaper plans), and a
whole-model quantized AlexNet/VGG forward smoke.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import autotune, ops
from repro.kernels.conv_pipe import conv_pipe
from repro.kernels.matmul_pipe import matmul_pipe
from repro.models.cnn import cnn_forward_stage, fuse_plan, init_cnn_params
from repro.quant import (QMAX, abs_max_scale, calibrate_cnn, dequantize,
                         dequantize_blocks, fake_quant, quantize,
                         quantize_blocks, quantize_channelwise)
from repro.quant import ref as qref
from repro.quant.calibrate import QuantizedCNNParams

KEY = jax.random.key(17)


def _rand(shape, key=KEY, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _quant_conv_operands(B, H, C, K, M, *, groups=1):
    x = _rand((B, H, H, C))
    w = _rand((K, K, C // groups, M), scale=0.2)
    b = _rand((M,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    return quantize(x, sx), wq, b, ws * sx


# ---------------------------------------------------------------------------
# quantization core (the one codepath — also used by optim.compress)
# ---------------------------------------------------------------------------

def test_roundtrip_error_bounded_by_half_step():
    x = _rand((64, 33))
    s = abs_max_scale(x)
    err = jnp.abs(dequantize(quantize(x, s), s) - x)
    assert float(jnp.max(err)) <= float(s) / 2 + 1e-7


def test_quantize_is_symmetric_and_clipped():
    s = abs_max_scale(jnp.array([1.0]))
    q = quantize(jnp.array([5.0, -5.0, 0.0]), s)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), [QMAX, -QMAX, 0])


def test_channelwise_scales_per_output_feature():
    w = _rand((3, 3, 4, 8), scale=0.3)
    wq, ws = quantize_channelwise(w, axis=-1)
    assert wq.shape == w.shape and wq.dtype == jnp.int8
    assert ws.shape == (8,)
    # each channel's max code hits 127 (scales are per-channel tight)
    assert int(jnp.min(jnp.max(jnp.abs(wq), axis=(0, 1, 2)))) == QMAX


def test_fake_quant_equals_dequantized_codes():
    x = _rand((16, 16))
    s = abs_max_scale(x)
    np.testing.assert_array_equal(
        np.asarray(fake_quant(x, s)),
        np.asarray(dequantize(quantize(x, s), s)))


def test_block_quantization_roundtrip_and_shapes():
    g = _rand((7, 13))          # 91 elements: tail block is padded
    q, s = quantize_blocks(g, 32)
    assert q.shape == (3, 32) and s.shape == (3, 1)
    back = dequantize_blocks(q, s, g.shape)
    assert back.shape == g.shape
    assert float(jnp.max(jnp.abs(back - g))) <= float(jnp.max(s)) / 2 + 1e-7


def test_compress_delegates_to_shared_core():
    """optim.compress must route through quant.core (one codepath)."""
    from repro.optim import compress
    g = _rand((100,))
    q1, s1 = compress._quantize(g)
    q2, s2 = quantize_blocks(g, compress.BLOCK)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ---------------------------------------------------------------------------
# int8 conv kernel parity vs the exact int32 reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool,groups,b_blk,oh_blk", [
    (None, 1, 1, 0),          # plain conv, full height
    (None, 1, 2, 4),          # batch-folded + H-tiled
    ("max", 1, 1, 4),         # fused pool across tile boundaries
    ("max", 2, 3, 4),         # grouped (AlexNet towers) + batch fold
    ("avg", 1, 2, 2),         # avg pool epilogue
])
def test_conv_int8_bit_exact_vs_reference(pool, groups, b_blk, oh_blk):
    """int8 in, int8 out: the Pallas kernel's int32 accumulation +
    requantize epilogue must match the exact-int reference BIT FOR BIT
    (no allclose — integer accumulation has no float slack)."""
    xq, wq, b, scale = _quant_conv_operands(5, 17, 6, 3, 16, groups=groups)
    kw = dict(stride=2, pad=1, pool=pool, pool_k=3, pool_s=2,
              groups=groups, out_scale=0.05)
    want = qref.conv_int8_ref(xq, wq, b, scale, **kw)
    got = conv_pipe(xq, wq, b, scale=scale, c_blk=2, m_blk=4,
                    oh_blk=oh_blk, b_blk=b_blk, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_int8_fp32_output_mode():
    """out_scale=None keeps the requantized-but-unquantized fp32 result
    (the classifier head mode)."""
    xq, wq, b, scale = _quant_conv_operands(2, 12, 4, 3, 8)
    want = qref.conv_int8_ref(xq, wq, b, scale, pad=1, out_scale=None)
    got = conv_pipe(xq, wq, b, scale=scale, pad=1, out_scale=None,
                    c_blk=2, m_blk=4, oh_blk=4)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_conv_int8_matches_fake_quant_within_one_code():
    """The fp32 fake-quant model and the exact-int path may differ only
    by float-rounding a borderline value to a neighbouring code."""
    x = _rand((2, 13, 13, 4))
    w = _rand((3, 3, 4, 8), scale=0.2)
    b = _rand((8,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    out_scale = 0.04
    got = qref.conv_int8_ref(quantize(x, sx), wq, b, ws * sx, pad=1,
                             out_scale=out_scale)
    fq = qref.conv_fake_quant_ref(x, w, b, x_scale=sx, w_scale=ws, pad=1,
                                  out_scale=out_scale)
    diff_codes = np.abs(np.asarray(dequantize(got, out_scale)) -
                        np.asarray(fq)) / out_scale
    assert diff_codes.max() <= 1.0 + 1e-6


def test_fc_int8_bit_exact_vs_reference():
    x = _rand((9, 50))
    w = _rand((50, 20), scale=0.2)
    b = _rand((20,), scale=0.1)
    sx = float(abs_max_scale(x))
    xq, (wq, ws) = quantize(x, sx), quantize_channelwise(w, axis=-1)
    scale = ws * sx
    for relu, out_scale in ((True, 0.03), (False, None)):
        want = qref.fc_int8_ref(xq, wq, b, scale, relu=relu,
                                out_scale=out_scale)
        got = matmul_pipe(xq, wq, b, scale=scale, out_scale=out_scale,
                          relu=relu, bm=4, bn=8, bk=16)
        if out_scale is not None:
            assert got.dtype == jnp.int8
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            assert got.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)


def test_ops_wrappers_route_quant_paths():
    """ops.fused_conv with a requantize scale: pallas and reference
    paths agree bit-for-bit through the jit'd public wrapper."""
    xq, wq, b, scale = _quant_conv_operands(3, 12, 4, 3, 8)
    kw = dict(scale=scale, pad=1, pool="max", out_scale=0.05)
    np.testing.assert_array_equal(
        np.asarray(ops.fused_conv(xq, wq, b, use_pallas=True, c_blk=2,
                                  m_blk=4, oh_blk=4, **kw)),
        np.asarray(ops.fused_conv(xq, wq, b, use_pallas=False, **kw)))


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def _smoke_setup(name="vgg16", n_calib=4):
    cfg = get_config(name).smoke()
    params = init_cnn_params(KEY, cfg)
    calib = _rand((n_calib, cfg.input_hw, cfg.input_hw, cfg.input_ch),
                  key=jax.random.key(5))
    return cfg, params, calib


def test_calibration_deterministic():
    cfg, params, calib = _smoke_setup()
    qp1 = calibrate_cnn(params, calib, cfg)
    qp2 = calibrate_cnn(params, calib, cfg)
    assert qp1.in_scale == qp2.in_scale
    for a, b in zip(qp1.layers, qp2.layers):
        if a is None:
            assert b is None
            continue
        assert (a.x_scale, a.y_scale) == (b.x_scale, b.y_scale)
        if a.w_q is not None:
            np.testing.assert_array_equal(np.asarray(a.w_q),
                                          np.asarray(b.w_q))


def test_calibration_structure():
    cfg, params, calib = _smoke_setup("alexnet")
    qp = calibrate_cnn(params, calib, cfg)
    assert len(qp.layers) == len(cfg.layers)
    convs = [l for l in qp.layers if l is not None and l.kind == "conv"]
    assert all(l.w_q.dtype == jnp.int8 for l in convs)
    assert all(l.y_scale is not None for l in convs)
    # per-channel weight scales: one per output feature
    assert all(l.w_scale.shape == (l.w_q.shape[-1],) for l in convs)
    # the final classifier keeps fp32 logits
    last_fc = qp.layers[-1]
    assert last_fc.kind == "fc" and last_fc.y_scale is None
    # scales are compile-time constants (python floats), not tracers
    assert isinstance(qp.in_scale, float)
    assert all(isinstance(l.x_scale, float) for l in convs)


def test_calibration_multi_batch_accumulates_range():
    """A second, larger-range batch must widen the observed scales."""
    cfg, params, calib = _smoke_setup()
    qp1 = calibrate_cnn(params, calib, cfg)
    qp2 = calibrate_cnn(params, [calib, 3.0 * calib], cfg)
    assert qp2.in_scale > qp1.in_scale


def test_quantized_params_are_a_pytree():
    cfg, params, calib = _smoke_setup()
    qp = calibrate_cnn(params, calib, cfg)
    leaves = jax.tree.leaves(qp)
    assert any(l.dtype == jnp.int8 for l in leaves)
    rebuilt = jax.tree.unflatten(jax.tree.structure(qp), leaves)
    assert isinstance(rebuilt, QuantizedCNNParams)
    assert rebuilt.in_scale == qp.in_scale


# ---------------------------------------------------------------------------
# dtype-aware autotuning
# ---------------------------------------------------------------------------

def test_plan_cache_keyed_by_dtype():
    autotune.clear_registry()
    base = dict(h=28, w=28, c=64, kh=3, kw=3, m=128, pad=1)
    p_fp = autotune.get_plan(autotune.ConvShape(**base))
    p_q = autotune.get_plan(autotune.ConvShape(**base, dtype="int8"))
    assert len(autotune.registry_snapshot()) == 2
    # int8 models strictly faster (4x bytes, 2x op rate)
    assert p_q.t_model < p_fp.t_model
    autotune.clear_registry()


def test_int8_vmem_model_shrinks_streamed_tiles():
    base = dict(h=16, w=16, c=16, kh=3, kw=3, m=32, pad=1)
    v_fp = autotune.conv_vmem_bytes(autotune.ConvShape(**base), 8, 16, 4)
    v_q = autotune.conv_vmem_bytes(
        autotune.ConvShape(**base, dtype="int8"), 8, 16, 4)
    # x/w/out tiles shrink 4x; the int32 accumulator and fp32 bias+scale
    # do not — so the total shrinks, but by less than 4x
    assert v_q < v_fp
    assert v_q > v_fp / 4


def test_int8_halves_modeled_time_on_bandwidth_bound_layer():
    """Acceptance: on a bandwidth-bound layer the int8 model must be
    <= 0.5x fp32 (4x less traffic; 2x op rate caps compute-bound at
    exactly 0.5x)."""
    # AlexNet conv3 geometry — weight-traffic bound at batch 1
    base = dict(h=13, w=13, c=256, kh=3, kw=3, m=384, pad=1)
    p_fp = autotune.get_plan(autotune.ConvShape(**base))
    tc, tm = autotune.score_plan(autotune.ConvShape(**base), p_fp.c_blk,
                                 p_fp.m_blk, p_fp.oh_blk, p_fp.b_blk)
    assert tm >= tc                    # genuinely bandwidth-bound
    p_q = autotune.get_plan(autotune.ConvShape(**base, dtype="int8"))
    assert p_q.t_model <= 0.5 * p_fp.t_model


def test_tuned_int8_plan_runs_and_matches_reference():
    """End to end: tune an int8 layer, run conv_pipe with the plan."""
    s = autotune.ConvShape(h=19, w=19, c=6, kh=3, kw=3, m=16, pad=1,
                           pool="max", pool_k=3, pool_s=2, dtype="int8")
    plan = autotune.best_plan(s, vmem_budget=512 * 1024)   # force tiling
    assert plan.vmem_bytes <= 512 * 1024
    xq, wq, b, scale = _quant_conv_operands(1, 19, 6, 3, 16)
    kw = dict(pad=1, pool="max", pool_k=3, pool_s=2, out_scale=0.05)
    np.testing.assert_array_equal(
        np.asarray(conv_pipe(xq, wq, b, scale=scale, c_blk=plan.c_blk,
                             m_blk=plan.m_blk, oh_blk=plan.oh_blk, **kw)),
        np.asarray(qref.conv_int8_ref(xq, wq, b, scale, **kw)))


# ---------------------------------------------------------------------------
# whole-model quantized forward
# ---------------------------------------------------------------------------

def test_quantized_vgg_pallas_bit_equals_reference(forward):
    """VGG has no LRN, so the quantized pallas path and the exact-int
    reference path agree on every int8 code — the whole model is
    integer-deterministic (logits: tight fp32 allclose)."""
    cfg, params, calib = _smoke_setup()
    qp = calibrate_cnn(params, calib, cfg)
    x = _rand((3, cfg.input_hw, cfg.input_hw, cfg.input_ch),
              key=jax.random.key(9))
    y_ref = forward(qp, x, cfg, use_pallas=False)
    y_pal = forward(qp, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_quantized_alexnet_forward_smoke(forward):
    """Whole-model quantized AlexNet (groups + LRN + fused pool) through
    both paths: finite logits, near-fp32 argmax; the params' type
    selects the int8 pipeline."""
    cfg, params, calib = _smoke_setup("alexnet")
    qp = calibrate_cnn(params, calib, cfg)
    x = _rand((8, cfg.input_hw, cfg.input_hw, cfg.input_ch),
              key=jax.random.key(9))
    y_fp = forward(params, x, cfg)
    y_q = forward(qp, x, cfg)                      # int8 by params type
    y_qp = forward(qp, x, cfg, use_pallas=True)
    for y in (y_q, y_qp):
        assert y.shape == y_fp.shape and y.dtype == jnp.float32
        assert np.isfinite(np.asarray(y)).all()
    agree = np.mean(np.argmax(np.asarray(y_q), -1)
                    == np.argmax(np.asarray(y_fp), -1))
    assert agree >= 0.8                            # loose CI bound
    # relative logit error stays small (calibration did its job)
    rel = (np.linalg.norm(np.asarray(y_q - y_fp))
           / np.linalg.norm(np.asarray(y_fp)))
    assert rel < 0.15


def test_quant_config_rejects_uncalibrated_params(forward):
    """A spec's precision must match the params: compiling calibrated
    int8 params under quant='none' fails loudly instead of silently
    serving them as something else."""
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn
    cfg, params, calib = _smoke_setup()
    x = _rand((2, cfg.input_hw, cfg.input_hw, cfg.input_ch))
    qp = calibrate_cnn(params, calib, cfg)
    with pytest.raises(ValueError, match="quant='int8'"):
        compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=2)), qp,
                    with_engine=False)
    # the same params serve fine under the int8 precision
    assert np.isfinite(np.asarray(forward(qp, x, cfg))).all()


def test_int8_forward_calls_the_one_wrapper_per_kernel():
    """The int8 compiled forward runs the same jitted kernel wrappers as
    the float one — ``fused_conv``, ``fc`` and ``lrn``, the kernel
    families the benchmark's device trace reads — and no int8 twin."""
    import re

    from repro.pipeline import ExecutionSpec, Precision, Serving, compile_cnn
    cfg, params, calib = _smoke_setup("alexnet")
    qp = calibrate_cnn(params, calib, cfg)
    c = compile_cnn(cfg, ExecutionSpec(precision=Precision(quant="int8"),
                                       serving=Serving(batch=2)), qp,
                    with_engine=False)
    x = jax.ShapeDtypeStruct((2, cfg.input_hw, cfg.input_hw, cfg.input_ch),
                             jnp.float32)
    names = set(re.findall(r"\bname=(\w+)",
                           str(jax.make_jaxpr(c._single_forward())(qp, x))))
    assert {"fused_conv", "fc", "lrn"} <= names, names
    assert not names & {"fused_conv_q", "fc_q"}, names


def test_quantized_forward_under_jit():
    """The serving path jit-closes over QuantizedCNNParams (pytree) and
    static scales; compile once, run twice."""
    cfg, params, calib = _smoke_setup()
    qp = calibrate_cnn(params, calib, cfg)
    fwd = jax.jit(lambda p, x: jnp.argmax(
        cnn_forward_stage(p, x, cfg, fuse_plan(cfg)), -1))
    x = _rand((4, cfg.input_hw, cfg.input_hw, cfg.input_ch))
    np.testing.assert_array_equal(np.asarray(fwd(qp, x)),
                                  np.asarray(fwd(qp, x)))


# ---------------------------------------------------------------------------
# the perf-gate satellite: new rows are informational, not failures
# ---------------------------------------------------------------------------

def test_check_against_reports_new_rows(tmp_path):
    import json
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.run import check_against

    committed = {"old_model": {"us_per_call": 10.0},
                 "stale_model": {"us_per_call": 1.0}}
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(committed))
    rows = {"old_model": {"us_per_call": 10.5},       # within 10%
            "new_int8_model": {"us_per_call": 5.0},   # no baseline: new
            "summary_row": {"speedup": 2.0}}          # non-model: ignored
    regressions, new = check_against(str(p), rows)
    assert regressions == []
    assert len(new) == 1 and "new_int8_model" in new[0]
    # and a genuine regression still fails
    rows["old_model"]["us_per_call"] = 12.0
    regressions, new = check_against(str(p), rows)
    assert len(regressions) == 1 and "old_model" in regressions[0]
