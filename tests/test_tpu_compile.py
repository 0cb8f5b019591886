"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each test lowers a kernel with
``interpret=False`` against a *described* ``v5e:2x2`` topology and has
the TPU compiler (Mosaic) compile it, which refuses exactly what the chip
would — misaligned blocks, strided value slices, 1-D gathers, VMEM over
the scoped limit. The compiled HLO must hold the kernel as a
``tpu_custom_call``.

The topology is described inside a module fixture (only one process may
load the TPU library, so never at import), and every test of this kind
lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.autotune import ConvShape, GemmShape, get_gemm_plan, \
    get_plan
from repro.kernels.conv_pipe import conv_pipe, s2d_geometry
from repro.kernels.lrn_pwl import lrn_pwl
from repro.kernels.matmul_pipe import matmul_pipe
from repro.serve.stage_planner import group_io_shapes

BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but not read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    """``sds(shape, dtype)``: an argument placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_kernel(f, *args) -> None:
    txt = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


def _conv_args(sds, x_shape, w_shape, int8):
    m = w_shape[-1]
    dt = jnp.int8 if int8 else jnp.float32
    args = [sds(x_shape, dt), sds(w_shape, dt), sds((m,), jnp.float32)]
    if int8:
        args.append(sds((m,), jnp.float32))          # requantize scale
    return args


def _conv_fn(int8, **kw):
    if int8:
        return lambda x, w, b, s: conv_pipe(x, w, b, scale=s,
                                            out_scale=0.05, interpret=False,
                                            **kw)
    return lambda x, w, b: conv_pipe(x, w, b, interpret=False, **kw)


# AlexNet conv1 (stride 4: space-to-depth), conv2 (two groups of 48
# channels), conv5 with its fused 3x3/2 max pool; VGG-16 conv1_2 + pool
# (224x224x64, H-tiled) and conv5_3 + pool (512 channels a tile: four
# 128-lane pool slabs)
CONV_LAYERS = {
    "alexnet_conv1": ((BATCH, 227, 227, 3), (11, 11, 3, 96),
                      dict(stride=4, pad=0)),
    "alexnet_conv2_grouped": ((BATCH, 27, 27, 96), (5, 5, 48, 256),
                              dict(stride=1, pad=2, groups=2)),
    "alexnet_conv5_pool": ((BATCH, 13, 13, 384), (3, 3, 192, 256),
                           dict(stride=1, pad=1, groups=2, pool="max",
                                pool_k=3, pool_s=2)),
    "vgg16_conv1_pool": ((1, 224, 224, 64), (3, 3, 64, 64),
                         dict(stride=1, pad=1, pool="max", pool_k=2,
                              pool_s=2, c_blk=64, m_blk=64, oh_blk=8)),
    "vgg16_conv5_pool": ((BATCH, 14, 14, 512), (3, 3, 512, 512),
                         dict(stride=1, pad=1, pool="max", pool_k=2,
                              pool_s=2, c_blk=128, m_blk=512, oh_blk=14,
                              b_blk=4)),
}


@pytest.mark.parametrize("layer,int8", [
    ("alexnet_conv1", False), ("alexnet_conv1", True),
    ("alexnet_conv2_grouped", False), ("alexnet_conv2_grouped", True),
    ("alexnet_conv5_pool", False), ("alexnet_conv5_pool", True),
    ("vgg16_conv1_pool", False), ("vgg16_conv5_pool", True)])
def test_conv_pipe_compiles(sds, layer, int8):
    x_shape, w_shape, kw = CONV_LAYERS[layer]
    _assert_kernel(_conv_fn(int8, **kw),
                   *_conv_args(sds, x_shape, w_shape, int8))


@pytest.mark.parametrize("int8", [False, True], ids=["fp32", "int8"])
def test_matmul_pipe_compiles_fc1(sds, int8):
    dt = jnp.int8 if int8 else jnp.float32
    args = [sds((BATCH, 9216), dt), sds((9216, 4096), dt),
            sds((4096,), jnp.float32)]
    if int8:
        args.append(sds((4096,), jnp.float32))
        f = lambda x, w, b, s: matmul_pipe(x, w, b, scale=s, out_scale=0.05,
                                           relu=True, bm=BATCH,
                                           interpret=False)
    else:
        f = lambda x, w, b: matmul_pipe(x, w, b, relu=True, bm=BATCH,
                                        interpret=False)
    _assert_kernel(f, *args)


def test_lrn_pwl_compiles(sds):
    _assert_kernel(lambda x: lrn_pwl(x, interpret=False),
                   sds((BATCH, 55, 55, 96), jnp.float32))


# (conv groups, fc layers, groups with folded column taps, groups with
# the pool in the kernel's epilogue)
NET_GROUPS = {"alexnet": (5, 3, 2, 1), "vgg16": (13, 3, 3, 5)}


@pytest.mark.parametrize("arch,dtype", [
    ("alexnet", "float32"), ("alexnet", "int8"), ("vgg16", "float32")],
    ids=["float32", "int8", "vgg16-float32"])
def test_tuned_alexnet_plans_compile(sds, arch, dtype):
    """Every plan the tuner picks for AlexNet and VGG-16 at the serving
    batch lowers: its lane/sublane-legal blocks and its VMEM model hold
    on the chip, AlexNet conv1's and conv2's and VGG conv1_1's, conv1_2's
    and conv2_1's over their folded column taps among them, and VGG's
    pooled groups at 256 and 512 output channels a tile (the epilogue's
    128-lane pool slabs; their int8 form is ``vgg16_conv5_pool``
    above)."""
    cfg = get_config(arch)
    int8 = dtype == "int8"
    n_conv = n_fc = n_folded = n_pooled = 0
    for group, in_shape, out_shape in group_io_shapes(cfg):
        layer = cfg.layers[group[0]]
        if layer.kind == "conv":
            h, w, c = in_shape
            pool = cfg.layers[group[1]] if len(group) == 2 else None
            shape = ConvShape(
                h=h, w=w, c=c, kh=layer.kernel, kw=layer.kernel,
                m=layer.out_ch, stride=layer.stride, pad=layer.pad,
                groups=layer.groups, pool=pool.pool if pool else None,
                pool_k=pool.kernel if pool else 2,
                pool_s=pool.stride if pool else 2, dtype=dtype, b=BATCH)
            plan = get_plan(shape, vmem_budget=cfg.vmem_budget)
            n_folded += s2d_geometry(h, w, c // layer.groups, layer.kernel,
                                     layer.kernel, stride=layer.stride,
                                     pad=layer.pad).kw_fold > 1
            n_pooled += pool is not None
            f = _conv_fn(int8, stride=shape.stride, pad=shape.pad,
                         groups=shape.groups, pool=shape.pool,
                         pool_k=shape.pool_k, pool_s=shape.pool_s,
                         c_blk=plan.c_blk, m_blk=plan.m_blk,
                         oh_blk=plan.oh_blk, b_blk=plan.b_blk)
            _assert_kernel(f, *_conv_args(
                sds, (BATCH, h, w, c),
                (layer.kernel, layer.kernel, c // layer.groups,
                 layer.out_ch), int8))
            n_conv += 1
        elif layer.kind == "fc":
            k = 1
            for d in in_shape:
                k *= d
            n = out_shape[-1]
            plan = get_gemm_plan(GemmShape(m=BATCH, k=k, n=n, dtype=dtype),
                                 vmem_budget=cfg.vmem_budget)
            blocks = dict(bm=plan.bm, bn=plan.bn, bk=plan.bk,
                          interpret=False)
            dt = jnp.int8 if int8 else jnp.float32
            args = [sds((BATCH, k), dt), sds((k, n), dt),
                    sds((n,), jnp.float32)]
            if int8:
                args.append(sds((n,), jnp.float32))
                f = lambda x, w, b, s: matmul_pipe(x, w, b, scale=s,
                                                   **blocks)
            else:
                f = lambda x, w, b: matmul_pipe(x, w, b, **blocks)
            _assert_kernel(f, *args)
            n_fc += 1
    assert (n_conv, n_fc, n_folded, n_pooled) == NET_GROUPS[arch]


@pytest.mark.parametrize("arch,families,n_kernels", [
    ("alexnet", ["fc", "fused_conv", "lrn"], 10),   # 5 conv, 2 LRN, 3 FC
    ("vgg16", ["fc", "fused_conv"], 16)],           # 13 conv, 3 FC
    ids=["alexnet", "vgg16"])
def test_whole_alexnet_forward_names_its_kernels(topo, sds, arch, families,
                                                 n_kernels):
    """``CompiledCNN.lower`` of the whole AlexNet (and VGG-16) forward
    (with the span log on, as it is by default): every compiled Pallas
    kernel is an instruction named after its jitted wrapper,
    ``fused_conv``, ``fc`` or ``lrn``, the families the benchmark's
    kernel rooflines read from the device trace."""
    import re

    from repro.models.cnn import init_cnn_params
    from repro.obs import SPANS
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn

    assert SPANS.enabled
    cfg = get_config(arch)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: init_cnn_params(jax.random.key(0), cfg)))
    compiled = compile_cnn(cfg, ExecutionSpec(
        interpret=False, serving=Serving(batch=BATCH)), params,
        with_engine=False)
    txt = compiled.lower(sds((BATCH, cfg.input_hw, cfg.input_hw,
                              cfg.input_ch), jnp.float32)).compile().as_text()
    kernels = [re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", line).group(1)
               for line in txt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted({k.split(".")[0] for k in kernels}) == families, kernels
    assert len(kernels) == n_kernels, kernels
