"""Compile-once pipeline API tests: ExecutionSpec cross-validation,
CNNConfig construction-time validation, compile idempotence (shared
registry-cached plans, zero re-sweeps), plan-table JSON round-trip
byte-equality, load-plan-skips-the-sweep, forward/stage parity vs the
pre-refactor paths (fp32 allclose, int8 bit-exact), interpret_mode
scoping, and all four execution modes on 8 virtual devices."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import CNNConfig
from repro.kernels import autotune, ops
from repro.models.cnn import (cnn_forward, cnn_forward_quant,
                              cnn_forward_stage, fuse_plan,
                              init_cnn_params)
from repro.pipeline import (CompiledCNN, ExecutionSpec, Placement, PlanTable,
                            Precision, Serving, Tiling, compile_cnn,
                            load_plan, resolve_config, spec_from_config)
from repro.serve import Request
from tests.test_parallel import run_in_mesh_subprocess

KEY = jax.random.key(5)


def _setup(name="alexnet", batch=4):
    cfg = get_config(name).smoke()
    params = init_cnn_params(KEY, cfg)
    x = jax.random.normal(KEY, (batch, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    return cfg, params, x


# ---------------------------------------------------------------------------
# spec validation (satellite: reject contradictory combinations early)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("build", [
    lambda: ExecutionSpec(precision=Precision(quant="fp4")),
    lambda: ExecutionSpec(precision=Precision(dtype="float16")),
    lambda: ExecutionSpec(precision=Precision(quant="int8",
                                              dtype="bfloat16")),
    lambda: ExecutionSpec(precision=Precision(quant="int8", calib=0)),
    lambda: ExecutionSpec(serving=Serving(batch=0)),
    lambda: ExecutionSpec(serving=Serving(clock="wall")),
    lambda: ExecutionSpec(serving=Serving(execute=False)),   # measured clock
    lambda: ExecutionSpec(serving=Serving(batch=8), tiling=Tiling(b_blk=3)),
    lambda: ExecutionSpec(placement=Placement(replicas=0)),
    lambda: ExecutionSpec(placement=Placement(microbatches=2)),
    lambda: ExecutionSpec(placement=Placement(pp_stages=2, microbatches=3),
                          serving=Serving(batch=8)),
])
def test_spec_rejects_contradictions(build):
    with pytest.raises(ValueError):
        build()


def test_spec_accepts_consistent_combinations():
    s = ExecutionSpec(
        precision=Precision(quant="int8"),
        tiling=Tiling(b_blk=4),
        placement=Placement(replicas=2, pp_stages=2, microbatches=4),
        serving=Serving(batch=8, clock="modeled", execute=False))
    assert s.mode == "hybrid" and s.run_dtype == "int8"


def test_spec_from_config_roundtrip():
    """spec_from_config and resolve_config are inverse on the knobs."""
    cfg = dataclasses.replace(get_config("alexnet"), serve_batch=16,
                              oh_blk=4, max_queue=7, replicas=2)
    spec = spec_from_config(cfg)
    rcfg = resolve_config(get_config("alexnet"), spec)
    assert rcfg == cfg
    assert spec.serving.batch == 16 and spec.placement.replicas == 2


# ---------------------------------------------------------------------------
# CNNConfig construction-time validation (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(quant="int4"), "none.*int8|int8"),
    (dict(quant="int8", calib=0), "calibration source"),
    (dict(pp_stages=99), "fusion groups"),
    (dict(replicas=0), ">= 1"),
    (dict(b_blk=3, serve_batch=8), "multiple of b_blk"),
])
def test_cnnconfig_rejects_bad_knobs(kw, match):
    cfg = get_config("alexnet")
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **kw)


def test_cnnconfig_group_count_matches_fuse_plan():
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name)
        assert cfg.n_fuse_groups == len(fuse_plan(cfg))


# ---------------------------------------------------------------------------
# compile idempotence + the plan registry
# ---------------------------------------------------------------------------

def test_compile_idempotent_zero_resweeps():
    """Two compiles of the same spec share registry-cached plans: the
    second performs no DSE sweep and freezes an identical table."""
    cfg, params, x = _setup()
    spec = ExecutionSpec(serving=Serving(batch=4))
    a = compile_cnn(cfg, spec, params)
    autotune.reset_sweep_stats()
    b = compile_cnn(cfg, spec, params)
    st = autotune.sweep_stats()
    assert st["conv_sweeps"] == 0 and st["gemm_sweeps"] == 0
    assert st["conv_hits"] > 0 and st["gemm_hits"] > 0
    assert a.plan_table == b.plan_table
    # the per-group plan objects are the SAME registry entries
    for g, plan in a.group_plans.items():
        assert b.group_plans[g] is plan


def test_plan_table_json_roundtrip_byte_equality():
    cfg, params, _ = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    text = c.plan_table.to_json()
    assert PlanTable.from_json(text).to_json() == text
    # and through the file API
    path = "/tmp/_pipe_plan_roundtrip.json"
    c.save_plan(path)
    assert load_plan(path).to_json() == text
    assert open(path).read() == text
    doc = json.loads(text)
    assert set(doc) == {"format", "conv", "gemm", "provenance"}


def test_load_plan_skips_dse_sweep():
    """The committed-artifact contract: a saved plan table seeds the
    registries, so a fresh compile performs ZERO sweeps (vs a cleared
    registry, which must sweep)."""
    cfg, params, _ = _setup()
    spec = ExecutionSpec(serving=Serving(batch=4))
    path = compile_cnn(cfg, spec, params).save_plan(
        "/tmp/_pipe_plan_seed.json")

    autotune.clear_registry()
    autotune.reset_sweep_stats()
    compile_cnn(cfg, spec, params, plan_path=path)
    st = autotune.sweep_stats()
    assert st["conv_sweeps"] == 0 and st["gemm_sweeps"] == 0

    autotune.clear_registry()
    autotune.reset_sweep_stats()
    compile_cnn(cfg, spec, params)
    st = autotune.sweep_stats()
    assert st["conv_sweeps"] > 0          # without the table it sweeps


def test_plan_table_format_back_compat():
    """format-1 (rows only) and format-2 (rows + provenance) documents
    load into the format-3 world; unknown formats are rejected."""
    cfg, params, _ = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    doc = json.loads(c.plan_table.to_json())
    assert doc["format"] == 3

    f1 = json.dumps({"format": 1, "conv": doc["conv"],
                     "gemm": doc["gemm"]})
    t1 = PlanTable.from_json(f1)
    assert t1 == c.plan_table            # provenance excluded from eq
    assert t1.provenance == {}
    assert json.loads(t1.to_json())["format"] == 3   # re-saves current

    f2 = json.dumps({"format": 2, "conv": doc["conv"],
                     "gemm": doc["gemm"],
                     "provenance": {"src": "committed"}})
    t2 = PlanTable.from_json(f2)
    assert t2 == c.plan_table
    assert t2.provenance == {"src": "committed"}

    with pytest.raises(ValueError, match="format"):
        PlanTable.from_json(
            json.dumps({"format": 99, "conv": [], "gemm": []}))


def test_plan_table_measured_roundtrip_byte_stable():
    """A format-3 table WITH measurements round-trips byte-identically;
    measurements attach by plan key, show up in the summary, and
    participate in table identity (unlike provenance)."""
    from repro.pipeline.plan_table import plan_key

    cfg, params, _ = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    tbl = c.plan_table
    assert "measured_plans" not in tbl.summary()     # unmeasured: absent

    k0 = plan_key(tbl.conv[0])
    rec = {"t_measured": 1.5e-4, "t_model_call": 3e-5, "interpret": True,
           "warmup": 1, "iters": 1, "repeats": 3, "trim": 1}
    prov = {"measurement": {"backend": {"platform": "cpu"}}}
    m = tbl.with_measurements({k0: rec}, provenance=prov)
    assert m.measurements() == {k0: rec}
    assert m.summary()["measured_plans"] == 1
    assert m != tbl               # measurements ARE part of identity

    text = m.to_json()
    again = PlanTable.from_json(text)
    assert again.to_json() == text
    assert again.provenance == prov
    # inheriting the same measurements verbatim is byte-stable — the
    # seeded-compile contract at the table level
    assert m.with_measurements(again.measurements(),
                               provenance=prov).to_json() == text


# ---------------------------------------------------------------------------
# forward parity vs the pre-refactor paths
# ---------------------------------------------------------------------------

def test_compiled_forward_matches_legacy_fold_fp32():
    """CompiledCNN.forward (frozen plans) vs the pre-refactor direct
    fold over fuse_plan — identical math, pallas and ref paths."""
    cfg, params, x = _setup("alexnet")
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    want = cnn_forward_stage(params, x, cfg, fuse_plan(cfg),
                             use_pallas=True)
    np.testing.assert_allclose(np.asarray(c.forward(x)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # the deprecation shim must agree exactly (same plans, same kernels)
    np.testing.assert_array_equal(
        np.asarray(cnn_forward(params, x, cfg, use_pallas=True)),
        np.asarray(c.forward(x)))


def test_compiled_forward_matches_legacy_fold_vgg_ref_path():
    cfg, params, x = _setup("vgg16", batch=2)
    spec = ExecutionSpec(serving=Serving(batch=2), use_pallas=False)
    c = compile_cnn(cfg, spec, params)
    want = cnn_forward_stage(params, x, cfg, fuse_plan(cfg),
                             use_pallas=False)
    np.testing.assert_allclose(np.asarray(c.forward(x)),
                               np.asarray(want), rtol=1e-6, atol=1e-6)


def test_compiled_forward_int8_bit_exact_vs_legacy():
    """The quantized compile (calibration inside the compile phase) is
    BIT-exact vs the pre-refactor cnn_forward_quant on the same
    calibrated params."""
    from repro.quant import calibrate_cnn
    cfg, params, x = _setup("alexnet")
    spec = ExecutionSpec(precision=Precision(quant="int8"),
                         serving=Serving(batch=4))
    c = compile_cnn(cfg, spec, (params, x))
    qp = calibrate_cnn(params, x, cfg)
    want = cnn_forward_quant(qp, x, cfg, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(c.forward(x)),
                                  np.asarray(want))


def test_forward_stage_chain_matches_forward():
    cfg, params, x = _setup("alexnet")
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    h = x
    for i in range(c.n_stages):
        h = c.forward_stage(i, h)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(c.forward(x)))


def test_compiled_serve_returns_report_with_completions():
    cfg, params, x = _setup("alexnet")
    spec = ExecutionSpec(serving=Serving(batch=4, clock="modeled"))
    c = compile_cnn(cfg, spec, params)
    reqs = [Request(rid=i, t_arrival=0.0, image=np.asarray(x[i % 4]))
            for i in range(9)]
    rep = c.serve(reqs)
    assert rep.n_done == 9 and len(rep.completions) == 9
    assert "completions" not in rep.to_dict()     # summary stays small
    want = np.asarray(jnp.argmax(c.forward(x), -1))
    preds = {cm.rid: cm.pred for cm in rep.completions}
    assert all(preds[i] == int(want[i % 4]) for i in range(9))


# ---------------------------------------------------------------------------
# compile-time precision/source checks
# ---------------------------------------------------------------------------

def test_compile_rejects_mismatched_precision_sources():
    from repro.quant import calibrate_cnn
    cfg, params, x = _setup()
    qp = calibrate_cnn(params, x, cfg)
    with pytest.raises(ValueError, match="quant"):      # quantized params,
        compile_cnn(cfg, ExecutionSpec(), qp)           # fp32 spec
    with pytest.raises(ValueError, match="calibration"):  # calib batch,
        compile_cnn(cfg, ExecutionSpec(), x)              # fp32 spec


def test_compile_accepts_prequantized_params():
    from repro.quant import calibrate_cnn
    cfg, params, x = _setup()
    qp = calibrate_cnn(params, x, cfg)
    spec = ExecutionSpec(precision=Precision(quant="int8"),
                         serving=Serving(batch=4))
    c = compile_cnn(cfg, spec, qp)
    want = cnn_forward_quant(qp, x, cfg, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(c.forward(x)),
                                  np.asarray(want))


# ---------------------------------------------------------------------------
# the committed serving artifact: CompiledCNN.save / load
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_fp32_byte_stable(tmp_path):
    """save -> load -> save produces byte-identical plan_table.json and
    manifest.json; params restore exactly; spec and forward agree."""
    cfg, params, x = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    a1 = tmp_path / "art1"
    c.save(a1)
    assert (a1 / "_COMMITTED").exists()
    c2 = CompiledCNN.load(a1)
    assert c2.spec == c.spec and c2.cfg == c.cfg
    assert c2.plan_table.to_json() == c.plan_table.to_json()
    for a, b in zip(jax.tree.leaves(c.params), jax.tree.leaves(c2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c.forward(x)),
                                  np.asarray(c2.forward(x)))
    a2 = tmp_path / "art2"
    c2.save(a2)
    for f in ("plan_table.json", "manifest.json"):
        assert (a1 / f).read_bytes() == (a2 / f).read_bytes(), f


def test_artifact_load_is_zero_sweep(tmp_path):
    """A loaded artifact seeds the plan registries: the warm compile
    inside load() performs ZERO DSE sweeps."""
    cfg, params, _ = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    c.save(tmp_path / "art")
    autotune.clear_registry()
    autotune.reset_sweep_stats()
    CompiledCNN.load(tmp_path / "art")
    st = autotune.sweep_stats()
    assert st["conv_sweeps"] == 0 and st["gemm_sweeps"] == 0
    assert st["conv_hits"] > 0 and st["gemm_hits"] > 0


def test_artifact_roundtrip_int8_bit_exact(tmp_path):
    cfg, params, x = _setup()
    spec = ExecutionSpec(precision=Precision(quant="int8"),
                         serving=Serving(batch=4))
    c = compile_cnn(cfg, spec, (params, x))
    c.save(tmp_path / "art")
    c2 = CompiledCNN.load(tmp_path / "art")
    assert c2.quant and c2.spec == c.spec
    np.testing.assert_array_equal(np.asarray(c.forward(x)),
                                  np.asarray(c2.forward(x)))


def test_artifact_uncommitted_or_corrupt_raises(tmp_path):
    from repro.ckpt.checkpoint import CheckpointError
    cfg, params, _ = _setup()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4)), params)
    p = tmp_path / "art"
    c.save(p)
    (p / "_COMMITTED").unlink()
    with pytest.raises(CheckpointError, match="committed"):
        CompiledCNN.load(p)
    (p / "_COMMITTED").write_text("ok")
    (p / "leaf_0.npy").write_bytes(b"\x93NUMPY truncated")
    with pytest.raises(CheckpointError, match="leaf 0"):
        CompiledCNN.load(p)


# ---------------------------------------------------------------------------
# interpret_mode: the scoped override of the backend-chosen mode
# ---------------------------------------------------------------------------

def test_interpret_mode_scopes_and_restores():
    assert ops.get_interpret() is True          # the CPU backend interprets
    with ops.interpret_mode(False):
        assert ops.get_interpret() is False
        with ops.interpret_mode(True):
            assert ops.get_interpret() is True
        assert ops.get_interpret() is False
    assert ops.get_interpret() is True


def test_interpret_mode_restores_on_exception():
    with pytest.raises(RuntimeError):
        with ops.interpret_mode(False):
            raise RuntimeError("boom")
    assert ops.get_interpret() is True


def test_compiled_threads_interpret_through_forward():
    """A spec pinning interpret=True runs inside interpret_mode — the
    compile's choice, not the surrounding scope, governs the run."""
    cfg, params, x = _setup()
    spec = ExecutionSpec(serving=Serving(batch=4), use_pallas=False,
                         interpret=True)
    c = compile_cnn(cfg, spec, params)
    seen = []
    # under a scope that would otherwise be False, the compiled ctx must
    # flip the mode back to the spec's choice for the duration
    with ops.interpret_mode(False):
        with c._ctx():
            seen.append(ops.get_interpret())
        seen.append(ops.get_interpret())
    assert seen == [True, False]
    assert ops.get_interpret() is True


def test_interpret_mode_is_part_of_the_wrapper_jit_key():
    """A trace taken in one mode is never reused in the other: each mode
    resolves at call time and keys a jit cache entry of its own."""
    x = jnp.ones((1, 4, 4, 8), jnp.float32)
    w = jnp.ones((3, 3, 8, 8), jnp.float32) * 0.1
    b = jnp.zeros((8,), jnp.float32)
    ops.fused_conv(x, w, b, pad=1, use_pallas=True)
    with ops.interpret_mode(False):
        # compiled Mosaic kernels cannot run on the CPU backend: reusing
        # the interpret-mode trace would silently succeed here instead
        with pytest.raises(Exception):
            jax.block_until_ready(
                ops.fused_conv(x, w, b, pad=1, use_pallas=True))


def test_interpret_true_on_tpu_backend_is_a_spec_error(monkeypatch):
    """On a TPU an explicit interpret=True would hide the device."""
    import repro.pipeline.compile as compile_mod
    from repro.core.config import SpecError
    cfg, params, _ = _setup()
    monkeypatch.setattr(compile_mod, "backend_interprets", lambda: False)
    with pytest.raises(SpecError, match="interpret"):
        compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=4),
                                       interpret=True), params)


# ---------------------------------------------------------------------------
# all four execution modes on 8 virtual devices (acceptance)
# ---------------------------------------------------------------------------

def test_all_four_modes_parity_on_8_devices():
    """fp32 single, int8 single, dp4 and pp4 through compile_cnn, each
    checked against its pre-refactor path: fp32 allclose, int8
    bit-exact, dp/pp predictions identical to the unsharded forward."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import (cnn_forward_quant, cnn_forward_stage,
                                      fuse_plan, init_cnn_params)
        from repro.pipeline import (ExecutionSpec, Placement, Precision,
                                    Serving, compile_cnn)
        from repro.quant import calibrate_cnn
        from repro.serve import Request

        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        x = jax.random.normal(key, (8, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        want = np.asarray(cnn_forward_stage(params, x, cfg, fuse_plan(cfg),
                                            use_pallas=True))

        # fp32 single
        c1 = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)),
                         params)
        np.testing.assert_allclose(np.asarray(c1.forward(x)), want,
                                   rtol=1e-5, atol=1e-5)

        # int8 single: bit-exact vs the pre-refactor quant path
        qp = calibrate_cnn(params, x, cfg)
        c8 = compile_cnn(cfg, ExecutionSpec(
            precision=Precision(quant='int8'),
            serving=Serving(batch=8)), qp)
        np.testing.assert_array_equal(
            np.asarray(c8.forward(x)),
            np.asarray(cnn_forward_quant(qp, x, cfg, use_pallas=True)))

        # dp4: served predictions == unsharded argmax
        cdp = compile_cnn(cfg, ExecutionSpec(
            placement=Placement(replicas=4),
            serving=Serving(batch=2, clock='modeled')), params)
        assert cdp.mesh is not None            # mesh built at compile
        reqs = [Request(rid=i, image=np.asarray(x[i]), t_arrival=0.0)
                for i in range(8)]
        rep = cdp.serve(reqs)
        assert rep.n_done == 8 and rep.rounds == 1
        preds = {c.rid: c.pred for c in rep.completions}
        amax = want.argmax(-1)
        assert all(preds[i] == int(amax[i]) for i in range(8))

        # pp4: device-resident stages, forward parity
        cpp = compile_cnn(cfg, ExecutionSpec(
            placement=Placement(pp_stages=4, microbatches=4),
            serving=Serving(batch=8, clock='modeled')), params)
        assert cpp.stage_plan is not None and cpp.n_stages == 4
        np.testing.assert_allclose(np.asarray(cpp.forward(x)), want,
                                   rtol=1e-5, atol=1e-5)
    """)
