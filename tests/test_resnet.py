"""ResNet-50 v1.5 through the program's normal path, on the CPU (Pallas in
interpret mode): the layer graph, the residual add in ``conv_pipe``'s
epilogue, strided 1x1 convs on the subsampled input, and what a graph
refuses."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import SpecError, flops_per_image, fuse_groups
from repro.kernels.conv_pipe import conv_pipe, s2d_geometry
from repro.kernels.ref import conv_pipe_ref
from repro.models import cnn
from repro.pipeline import (ExecutionSpec, Placement, Precision, Serving,
                            compile_cnn)
from tests.test_parallel import run_in_mesh_subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# logit_rel_err (the worst image's relative L2 error of its logits) of
# the smoke forward against the float32 reference. The kernels sum each
# conv in another order than lax.conv (taps folded into the contraction,
# C-tiles), which over 53 convs reads about 3e-07; the same reference
# with bfloat16 three-pass dots reads about 1e-05 here, so 2e-06 passes
# the first with room and refuses the second.
TOL = 2e-6
SEED = 2 ** 31 + 5


def _smoke_cell():
    """The benchmark's ``resnet50`` file over the program's smoke graph
    (widths / 16, 67 x 67 input), and that graph as a ``CNNConfig``."""
    from bench.systems.compiled_cnn import _program_layers

    small = get_config("resnet50").smoke()
    with open(os.path.join(ROOT, "bench", "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    return dict(cfg, layers=_program_layers(small),
                input={"hw": small.input_hw, "ch": small.input_ch}), small


def _rel_err(got, want):
    from bench.correct import logit_rel_err
    return logit_rel_err(np.asarray(got), np.asarray(want))


def test_config_is_resnet50_v1_5_at_published_widths():
    cfg = get_config("resnet50")
    kinds = [l.kind for l in cfg.layers]
    assert (cfg.input_hw, cfg.input_ch, cfg.n_classes) == (224, 3, 1000)
    assert len(kinds) == 72 and kinds.count("conv") == 53
    assert kinds.count("add") == 16
    assert sum(l.kernel == 1 for l in cfg.layers if l.kind == "conv") == 36
    assert flops_per_image(cfg) == 8_178_368_512
    params = jax.eval_shape(lambda: cnn.init_cnn_params(jax.random.key(0),
                                                        cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == 25_530_472
    # v1.5: every strided conv but the stem is a block's 3x3 or its
    # 1x1 projection
    strided = [(l.kernel, l.stride) for l in cfg.layers[1:]
               if l.kind == "conv" and l.stride > 1]
    assert sorted(strided) == [(1, 2)] * 3 + [(3, 2)] * 3


def test_fuse_groups_fold_each_add_into_the_conv_before_it():
    cfg = get_config("resnet50")
    groups = fuse_groups(cfg.layers)
    added = [g for g in groups if len(g) == 2]
    assert len(groups) == 56 and len(added) == 16
    assert all(cfg.layers[a].kind == "conv" and cfg.layers[b].kind == "add"
               for a, b in added)
    # a stage's first block: the add folds into the projection, and the
    # block's last 1x1 is the residual operand; the padded pool and gap
    # stay standalone
    assert (5, 6) in added and (4,) in groups
    assert (1,) in groups and (70,) in groups


@pytest.mark.parametrize("arch,groups", [
    ("alexnet", [(0,), (1,), (2,), (3,), (4,), (5,), (6,), (7,), (8, 9),
                 (10,), (11,), (12,)]),
    ("vgg16", [(0,), (1, 2), (3,), (4, 5), (6,), (7,), (8, 9), (10,),
               (11,), (12, 13), (14,), (15,), (16, 17), (18,), (19,),
               (20,)])])
def test_chain_groups_and_program_unchanged(arch, groups):
    """A chain keeps its groups, and its fold traces the program a plain
    pass of one activation from group to group traces."""
    cfg = get_config(arch).smoke()
    assert fuse_groups(cfg.layers) == groups
    params = cnn.init_cnn_params(jax.random.key(0), cfg)
    x = jnp.zeros((2, cfg.input_hw, cfg.input_hw, cfg.input_ch))

    def chain(p, x):
        for g in groups:
            x = cnn.run_group(p, x, cfg, g)
        return x

    fold = lambda p, x: cnn.cnn_forward_stage(p, x, cfg, groups)
    assert str(jax.make_jaxpr(fold)(params, x)) == \
        str(jax.make_jaxpr(chain)(params, x))


def test_smoke_forward_matches_the_reference(monkeypatch):
    """``compile_cnn(resnet50.smoke()).forward`` against
    ``bench/references/cnn_float`` on the benchmark's seeded weights;
    with one block's residual operand dropped it fails."""
    from bench.references import cnn_float
    from bench.weights import make_images, make_weights

    bcfg, small = _smoke_cell()
    weights = make_weights(bcfg, SEED)
    x = make_images(bcfg, SEED, 2)
    want = cnn_float.logits(bcfg, weights, x, block=2)
    spec = ExecutionSpec(serving=Serving(batch=2))
    got = compile_cnn(small, spec, weights, with_engine=False).forward(x)
    assert _rel_err(got, want) <= TOL

    run_group = cnn.run_group

    def drop_residual(p, x, cfg, group, **kw):
        if group == (22, 23):                   # stage 2, block 1
            kw["residual"] = jnp.zeros_like(kw["residual"])
        return run_group(p, x, cfg, group, **kw)

    monkeypatch.setattr(cnn, "run_group", drop_residual)
    bad = compile_cnn(small, spec, weights, with_engine=False).forward(x)
    assert _rel_err(bad, want) > 100 * TOL


@pytest.mark.parametrize("k,relu", [(1, True), (1, False), (3, True),
                                    (3, False)])
def test_conv_pipe_residual_epilogue(k, relu):
    """bias, then the residual, then ReLU in the epilogue, at an ow that
    ow_p pads (7 -> 8), over three H-tiles of 3 rows (the residual's
    last block padded) and an image block of 2 over a batch of 3."""
    kx, kw, kb, kr = jax.random.split(jax.random.key(k + 2 * relu), 4)
    x = jax.random.normal(kx, (3, 7, 7, 16))
    w = jax.random.normal(kw, (k, k, 16, 24)) * 0.2
    b = jax.random.normal(kb, (24,))
    r = jax.random.normal(kr, (3, 7, 7, 24))
    got = conv_pipe(x, w, b, pad=k // 2, relu=relu, residual=r, c_blk=16,
                    m_blk=24, oh_blk=3, b_blk=2, interpret=True)
    plain = conv_pipe_ref(x, w, b, pad=k // 2, relu=False)
    want = jnp.maximum(plain + r, 0.0) if relu else plain + r
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, conv_pipe_ref(x, w, b, pad=k // 2, relu=relu, residual=r),
        rtol=1e-5, atol=1e-5)


def test_residual_epilogue_takes_no_pool():
    x, w = jnp.ones((1, 8, 8, 4)), jnp.ones((1, 1, 4, 8))
    with pytest.raises(ValueError, match="pool"):
        conv_pipe(x, w, jnp.zeros(8), pool="max", residual=jnp.ones(
            (1, 8, 8, 8)), interpret=True)


@pytest.mark.parametrize("hw", [14, 13])
def test_strided_1x1_runs_on_the_subsampled_input(hw):
    """ResNet's 1x1/2 projection: the stride-1 conv over every other
    pixel, not space-to-depth's 4x channels of zero taps."""
    g = s2d_geometry(hw, hw, 64, 1, 1, stride=2, pad=0)
    assert (g.h, g.c, g.kh, g.kw, g.oh) == (-(-hw // 2), 64, 1, 1,
                                            (hw - 1) // 2 + 1)
    kx, kw = jax.random.split(jax.random.key(hw))
    x = jax.random.normal(kx, (2, hw, hw, 64))
    w = jax.random.normal(kw, (1, 1, 64, 32)) * 0.1
    got = conv_pipe(x, w, jnp.zeros(32), stride=2, relu=False,
                    interpret=True)
    want = jax.lax.conv_general_dilated(
        x, w, (2, 2), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spec,field", [
    (ExecutionSpec(precision=Precision(quant="int8")), "CNNConfig.quant"),
    (ExecutionSpec(placement=Placement(pp_stages=2)), "CNNConfig.pp_stages"),
], ids=["int8", "pp"])
def test_graph_refuses_int8_and_pipeline_stages(spec, field):
    cfg = get_config("resnet50").smoke()
    with pytest.raises(SpecError) as err:
        compile_cnn(cfg, spec, key=jax.random.key(0), with_engine=False)
    assert err.value.field == field and field in str(err.value)


def test_graph_refused_by_calibration_and_the_stage_planner():
    from repro.quant import calibrate_cnn
    from repro.serve.stage_planner import plan_stages

    cfg = get_config("resnet50").smoke()
    with pytest.raises(SpecError, match="layer graph") as err:
        calibrate_cnn([], jnp.zeros((1, 67, 67, 3)), cfg)
    assert err.value.field == "CNNConfig.quant"
    with pytest.raises(SpecError, match="layer graph") as err:
        plan_stages(cfg, 2)
    assert err.value.field == "CNNConfig.pp_stages"


def test_dp4_matches_the_single_device_logits():
    """``Placement(replicas=4)`` over 4 of 8 CPU devices: its
    ``shard_map`` wraps the whole graph fold."""
    run_in_mesh_subprocess("""
        import dataclasses
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Placement, Serving,
                                    compile_cnn)

        cfg = dataclasses.replace(get_config('resnet50').smoke(),
                                  input_hw=32)
        params = init_cnn_params(jax.random.key(4), cfg)
        x = np.asarray(jax.random.normal(jax.random.key(5),
                                         (8, 32, 32, 3)))
        one = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)),
                          params, with_engine=False).forward(x)
        dp = compile_cnn(cfg, ExecutionSpec(
            placement=Placement(replicas=4), serving=Serving(batch=2)),
            params)
        assert dp.mesh is not None and dp.mesh.devices.size == 4
        # each replica runs its 2 images at the plans of batch 2, so
        # the sums' order differs from batch 8's: compare as TOL does
        got, want = np.asarray(dp.forward(x)), np.asarray(one)
        err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
            want, axis=-1)
        assert err.max() <= 2e-6, err
    """)
