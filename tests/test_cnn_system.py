"""CNN reproduction behaviour tests: AlexNet/VGG-16 through the fused
pipeline, fused == unfused, pallas == ref, bandwidth model sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import flops_per_image
from repro.core.pipeline import bandwidth_model, fusion_savings
from repro.models.cnn import cnn_forward_stage, fuse_plan, init_cnn_params

KEY = jax.random.key(7)


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_cnn_smoke_forward(name, forward):
    cfg = get_config(name).smoke()
    params = init_cnn_params(KEY, cfg)
    x = jax.random.normal(KEY, (2, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    y = forward(params, x, cfg)
    assert y.ndim == 2 and y.shape[0] == 2
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_fused_equals_unfused(name):
    """PipeCNN's fusion is a dataflow change, not a math change: the
    reference fold over the fusion groups equals the fold over singleton
    (one layer each) groups."""
    cfg = get_config(name).smoke()
    params = init_cnn_params(KEY, cfg)
    x = jax.random.normal(KEY, (1, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    y_f = cnn_forward_stage(params, x, cfg, fuse_plan(cfg))
    y_u = cnn_forward_stage(params, x, cfg,
                            [(i,) for i in range(len(cfg.layers))])
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                               rtol=1e-4, atol=1e-4)


def test_pallas_pipeline_matches_ref_alexnet(forward):
    """Kernel path vs XLA path. The Pallas path uses the paper's PWL LRN
    (<=0.5% by design) while the ref path is exact LRN, so the tolerance
    accounts for the documented approximation propagating through layers."""
    cfg = get_config("alexnet").smoke()
    params = init_cnn_params(KEY, cfg)
    x = jax.random.normal(KEY, (1, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    y_ref = forward(params, x, cfg, use_pallas=False)
    y_pal = forward(params, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=5e-2, atol=5e-2)
    # and with LRN exactness isolated (VGG has no LRN): tight tolerance
    cfgv = get_config("vgg16").smoke()
    pv = init_cnn_params(KEY, cfgv)
    xv = jax.random.normal(KEY, (1, cfgv.input_hw, cfgv.input_hw,
                                 cfgv.input_ch), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(forward(pv, xv, cfgv, use_pallas=True)),
        np.asarray(forward(pv, xv, cfgv, use_pallas=False)),
        rtol=5e-4, atol=5e-4)


def test_flop_counts_match_paper():
    """Paper: 33.9 GOPS at 43 ms => ~1.46 GOP/image AlexNet; VGG-16 is
    ~30.9 GOP (conv+fc MACs x2). Our analytic counts must land there."""
    alex = flops_per_image(get_config("alexnet"))
    vgg = flops_per_image(get_config("vgg16"))
    assert 1.2e9 < alex < 1.7e9, f"AlexNet {alex/1e9:.2f} GOP"
    assert 29e9 < vgg < 32e9, f"VGG-16 {vgg/1e9:.2f} GOP"
    # paper consistency: time x throughput == ops
    assert abs(alex - 33.9e9 * 43e-3) / alex < 0.15


def test_fuse_plan_structure():
    cfg = get_config("vgg16")
    plan = fuse_plan(cfg)
    # VGG: every block's last conv fuses with its pool => 5 fused groups
    fused_groups = [g for g in plan if len(g) == 2]
    assert len(fused_groups) == 5
    cfg_a = get_config("alexnet")
    fused_a = [g for g in fuse_plan(cfg_a) if len(g) == 2]
    assert len(fused_a) == 1          # only conv5+pool is adjacent in AlexNet


def test_bandwidth_model_fusion_saves():
    """The paper's core claim, quantitatively: fused < unfused traffic,
    and the im2col-GEMM baseline ([4]) is far worse than both."""
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name)
        unf, fus, red = fusion_savings(cfg)
        assert fus < unf
        stages = bandwidth_model(cfg, fused=True)
        assert all(s.total > 0 for s in stages)
