"""Spatial tiling, batch folding, grouped conv and the DSE autotuner.

Covers the H-tiled conv_pipe (halo'd input tiles via element-indexed H)
against the oracle across tile sizes that do and don't divide OH, strides,
pool windows straddling tile boundaries, and AlexNet's two-tower grouped
convs — plus the batch-folded grid (b_blk images per grid step, the
serving path) and the autotuner's VMEM-budget guarantee at paper scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import autotune, ops, ref
from repro.kernels.conv_pipe import conv_pipe, conv_tile_geometry, \
    s2d_geometry
from repro.quant import abs_max_scale, quantize, quantize_channelwise
from repro.quant import ref as qref

KEY = jax.random.key(11)


def _rand(shape, key=KEY, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _check(B, H, C, K, M, *, stride=1, pad=0, pool=None, pool_k=2,
           pool_s=2, oh_blk=0, b_blk=1, groups=1, c_blk=4, m_blk=8,
           dtype=jnp.float32):
    x = _rand((B, H, H, C)).astype(dtype)
    w = _rand((K, K, C // groups, M), scale=0.2).astype(dtype)
    b = _rand((M,)).astype(dtype)
    got = conv_pipe(x, w, b, stride=stride, pad=pad, pool=pool,
                    pool_k=pool_k, pool_s=pool_s, c_blk=c_blk, m_blk=m_blk,
                    oh_blk=oh_blk, b_blk=b_blk, groups=groups)
    want = ref.conv_pipe_ref(x, w, b, stride=stride, pad=pad, pool=pool,
                             pool_k=pool_k, pool_s=pool_s, groups=groups)
    assert got.shape == want.shape
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


# ---------------------------------------------------------------------------
# H-tiling equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("oh_blk", [1, 3, 4, 7, 14, 0])
def test_oh_blk_dividing_and_not(oh_blk):
    """Tile depths that divide OH (14: 7,14), don't (3,4), degenerate (1)
    and full-height (0) all produce identical results."""
    _check(1, 16, 4, 3, 8, pad=1, oh_blk=oh_blk)            # OH = 16


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("oh_blk", [2, 5])
def test_strided_tiles(stride, oh_blk):
    _check(1, 23, 3, 5, 8, stride=stride, pad=2, oh_blk=oh_blk)


@pytest.mark.parametrize("pool,pool_k,pool_s", [
    ("max", 2, 2),        # non-overlapping windows
    ("max", 3, 2),        # AlexNet overlapping pool: windows straddle tiles
    ("avg", 3, 2),
])
@pytest.mark.parametrize("oh_blk", [2, 4, 6])
def test_pool_windows_straddling_tile_boundaries(pool, pool_k, pool_s,
                                                 oh_blk):
    """pool_k > pool_s makes every tile boundary a straddled window; the
    kernel recomputes the pool_k - pool_s conv halo rows per tile."""
    _check(1, 17, 4, 3, 8, pad=1, pool=pool, pool_k=pool_k, pool_s=pool_s,
           oh_blk=oh_blk)


def test_alexnet_conv1_geometry_tiled():
    _check(1, 27, 3, 11, 16, stride=4, pool="max", pool_k=3, pool_s=2,
           oh_blk=2, c_blk=3, m_blk=8)


def test_bfloat16_tiled():
    _check(1, 12, 4, 3, 8, pad=1, pool="max", oh_blk=4, dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# grouped conv inside the kernel (AlexNet two-tower shapes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("oh_blk", [0, 3, 4])
def test_grouped_conv_two_towers(oh_blk):
    """AlexNet conv2-like: groups=2, overlapping pool, C/G=4, M/G=8."""
    _check(1, 15, 8, 5, 16, pad=2, pool="max", pool_k=3, pool_s=2,
           oh_blk=oh_blk, groups=2)


def test_grouped_conv_unpadded_group_channels():
    """Per-group channel counts that don't divide c_blk/m_blk get padded
    per group (group slabs must stay aligned, not just the total)."""
    _check(1, 13, 6, 3, 30, pad=1, oh_blk=4, groups=3, c_blk=4, m_blk=4)


def _prims_outside_kernels(jaxpr):
    """Primitive names of a jaxpr and its sub-jaxprs, not descending
    into a ``pallas_call``'s kernel body (a folded layer concatenates
    its column-shifted windows there, in VMEM)."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            sub = getattr(param, "jaxpr", param)      # ClosedJaxpr or Jaxpr
            if hasattr(sub, "eqns"):
                names += _prims_outside_kernels(sub)
    return names


def test_grouped_conv_single_pallas_call_no_concat():
    """Acceptance: grouped conv is ONE pallas_call with no activation
    concatenate (the seed launched G kernels and concatenated)."""
    x = _rand((1, 15, 15, 8))
    w = _rand((3, 3, 4, 16), scale=0.2)
    b = _rand((16,))
    jaxpr = jax.make_jaxpr(
        lambda x, w, b: ops.fused_conv(
            x, w, b, pad=1, pool="max", pool_k=3, pool_s=2,
            use_pallas=True, groups=2, oh_blk=4))(x, w, b).jaxpr
    prims = _prims_outside_kernels(jaxpr)
    assert prims.count("pallas_call") == 1
    assert "concatenate" not in prims


# ---------------------------------------------------------------------------
# kw-tap folding: a narrow layer contracts over kw * c in kh dots
# ---------------------------------------------------------------------------

def _geometry(H, C, K, *, stride=1, pad=0, groups=1, **_):
    return s2d_geometry(H, H, C // groups, K, K, stride=stride, pad=pad)


@pytest.mark.parametrize("B,H,C,K,M,kw", [
    # conv1: 11x11/4 space-to-depth'd to 48 channels x 3 taps = 144
    (2, 27, 3, 11, 16, dict(stride=4, c_blk=144, m_blk=16)),
    # conv2: two groups of 48 channels x 5 taps = 240 (a c_blk of 128
    # runs as the one whole tile the folded taps need)
    (2, 9, 96, 5, 16, dict(pad=2, groups=2, c_blk=128, m_blk=8)),
    # a fused 3x3/2 max pool over H-tiles
    (1, 17, 8, 3, 8, dict(pad=1, pool="max", pool_k=3, pool_s=2,
                          oh_blk=4, c_blk=24)),
    # b_blk 2 does not divide a batch of 3
    (3, 12, 4, 3, 8, dict(pad=1, oh_blk=4, b_blk=2, c_blk=12)),
], ids=["conv1_s2d", "conv2_groups", "pool_tiled", "b_blk_partial"])
def test_kw_fold_matches_oracle(B, H, C, K, M, kw):
    g = _geometry(H, C, K, **kw)
    assert g.kw == 1 and g.kw_fold > 1
    _check(B, H, C, K, M, **kw)


def test_unfolded_taps_match_oracle():
    """96 channels x 3 taps take 3 MXU row passes folded or not, so the
    kernel keeps its column taps (the conv3-5 path at a small size)."""
    g = _geometry(9, 96, 3, pad=1)
    assert (g.c, g.kw, g.kw_fold) == (96, 3, 1)
    _check(2, 9, 96, 3, 8, pad=1, oh_blk=4, c_blk=96)


def test_kw_fold_int8_bit_equal():
    """int32 accumulation is exact in any order: a folded grouped,
    pooled layer equals the exact-int reference code for code."""
    x = _rand((3, 11, 11, 96))
    w = _rand((5, 5, 48, 16), scale=0.2)
    b = _rand((16,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    xq = quantize(x, sx)
    kw = dict(pad=2, groups=2, pool="max", pool_k=3, pool_s=2,
              out_scale=0.05)
    assert _geometry(11, 96, 5, **kw).kw_fold == 5
    got = conv_pipe(xq, wq, b, scale=ws * sx, c_blk=240, m_blk=8,
                    oh_blk=4, b_blk=2, **kw)
    want = qref.conv_int8_ref(xq, wq, b, ws * sx, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kw_fold_rule_on_alexnet():
    """The fold is decided from the shape: AlexNet conv1 and conv2 fold,
    conv3-5 (c >= 128) do not and keep the batch-32 plans they had
    before it; the static verifier re-proves the folded plans."""
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn

    cfg = get_config("alexnet")
    geoms = [s2d_geometry(s.h, s.w, s.c // s.groups, s.kh, s.kw,
                          stride=s.stride, pad=s.pad)
             for s in _conv_shapes(cfg)]
    assert [(g.c, g.kh, g.kw, g.kw_fold) for g in geoms] == [
        (432, 1, 1, 3), (240, 5, 1, 5), (256, 3, 3, 1), (192, 3, 3, 1),
        (192, 3, 3, 1)]
    params = jax.eval_shape(lambda: init_cnn_params(KEY, cfg))
    compiled = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=32)),
                           params, with_engine=False)
    plans = {g: (p.c_blk, p.m_blk, p.oh_blk, p.b_blk)
             for g, p in compiled.group_plans.items()
             if isinstance(p, autotune.ConvPlan)}
    assert plans[(0,)][0] == 432 and plans[(3,)][0] == 240
    assert {g: plans[g] for g in ((6,), (7,), (8, 9))} == {
        (6,): (128, 384, 13, 4), (7,): (192, 192, 13, 4),
        (8, 9): (192, 128, 4, 16)}
    assert compiled.verify() == []


# ---------------------------------------------------------------------------
# kh-tap folding: a narrow layer contracts over kh * kw * c in one dot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,C,K,M,kw", [
    # VGG-16 conv1_1 at a small H: 3 channels x 9 taps = 27
    (2, 12, 3, 3, 16, dict(pad=1, oh_blk=4, c_blk=27, m_blk=16)),
    # VGG-16 conv1_2 at a small H: 64 channels x 9 taps = 576
    (2, 10, 64, 3, 16, dict(pad=1, oh_blk=4, b_blk=2, c_blk=576,
                            m_blk=16)),
    # AlexNet conv1: 11x11/4 space-to-depth'd to 48 channels x 9 taps
    (2, 27, 3, 11, 16, dict(stride=4, oh_blk=2, c_blk=432, m_blk=16)),
    # VGG's fused 2x2/2 max pool over H-tiles, b_blk 2 of a batch of 3
    (3, 10, 64, 3, 64, dict(pad=1, pool="max", pool_k=2, pool_s=2,
                            oh_blk=4, b_blk=2, c_blk=576, m_blk=64)),
], ids=["conv1_1", "conv1_2", "conv1_s2d", "pool_tiled"])
def test_kh_fold_matches_oracle(B, H, C, K, M, kw):
    """The row taps fold on top of the column taps; the x tile's halo
    still spans every unfolded row tap."""
    g = _geometry(H, C, K, **kw)
    assert (g.kh, g.kw) == (1, 1) and g.kh_fold > 1 and g.kw_fold > 1
    _, _, oh_ext, hp_blk, _ = g.tiles(
        kw["oh_blk"], pool=kw.get("pool"), pool_k=kw.get("pool_k", 2),
        pool_s=kw.get("pool_s", 2))
    assert hp_blk == oh_ext + -(-K // kw.get("stride", 1)) - 1
    _check(B, H, C, K, M, **kw)


def test_kh_fold_int8_bit_equal():
    """A row-folded, pooled int8 layer (VGG conv1_2's shape at a small
    H) equals the exact-int reference code for code."""
    x = _rand((3, 10, 10, 64))
    w = _rand((3, 3, 64, 64), scale=0.2)
    b = _rand((64,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    xq = quantize(x, sx)
    kw = dict(pad=1, pool="max", pool_k=2, pool_s=2, out_scale=0.05)
    assert _geometry(10, 64, 3, **kw).kh_fold == 3
    got = conv_pipe(xq, wq, b, scale=ws * sx, c_blk=576, m_blk=64,
                    oh_blk=4, b_blk=2, **kw)
    want = qref.conv_int8_ref(xq, wq, b, ws * sx, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kh_fold_rule_on_vgg16():
    """VGG-16 conv1_1, conv1_2 and conv2_1 fold their row taps; the other
    ten conv groups (c >= 128) keep the batch-32 plans they had before
    the fold, byte for byte; the static verifier re-proves every plan."""
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn

    cfg = get_config("vgg16")
    geoms = [s2d_geometry(s.h, s.w, s.c // s.groups, s.kh, s.kw,
                          stride=s.stride, pad=s.pad)
             for s in _conv_shapes(cfg)]
    assert [(g.c, g.kh_fold) for g in geoms[:4]] == [
        (27, 3), (576, 3), (576, 3), (128, 1)]
    assert all(g.taps_folded == 1 for g in geoms[3:])
    params = jax.eval_shape(lambda: init_cnn_params(KEY, cfg))
    compiled = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=32)),
                           params, with_engine=False)
    plans = {g: (p.c_blk, p.m_blk, p.oh_blk, p.b_blk, p.vmem_bytes)
             for g, p in compiled.group_plans.items()
             if isinstance(p, autotune.ConvPlan)}
    assert [plans[g][0] for g in ((0,), (1, 2), (3,))] == [27, 576, 576]
    # the working set of a group whose halo the kernel makes counts its
    # line buffer: (4, 5), (8, 9) and conv5 (the others' would not fit)
    wide = {
        (4, 5): (128, 128, 16, 1, 10436608),
        (6,): (128, 256, 8, 4, 16236544),
        (7,): (128, 256, 8, 4, 16236544),
        (8, 9): (256, 256, 8, 2, 14598144),
        (10,): (256, 256, 4, 8, 16531456),
        (11,): (256, 256, 4, 8, 16531456),
        (12, 13): (256, 256, 4, 8, 16007168),
        (14,): (128, 512, 14, 4, 16613376),
        (15,): (128, 512, 14, 4, 16613376),
        (16, 17): (128, 512, 14, 4, 15695872)}
    assert {g: plans[g] for g in wide} == wide
    assert compiled.verify() == []


# ---------------------------------------------------------------------------
# the halo made in VMEM: a stride-1 conv's x goes in unpadded
# ---------------------------------------------------------------------------

def _halo_engaged(B, H, C, K, M, dtype="float32", **kw):
    s = autotune.ConvShape(h=H, w=H, c=C, kh=K, kw=K, m=M,
                           pad=kw.get("pad", 0), groups=kw.get("groups", 1),
                           pool=kw.get("pool"), pool_k=kw.get("pool_k", 2),
                           pool_s=kw.get("pool_s", 2), dtype=dtype, b=B)
    return autotune.halo_in_kernel(s, kw["c_blk"], kw["m_blk"],
                                   kw.get("oh_blk", 0), kw.get("b_blk", 1))


@pytest.mark.parametrize("B,H,C,K,M,kw", [
    # pad 1 over four H-tiles: a first, two interior, and a last whose
    # rows run past the pad (oh_blk 5 does not divide 16)
    (1, 16, 8, 3, 8, dict(pad=1, oh_blk=5, c_blk=8, m_blk=8)),
    # pad 2, 5x5 taps, over five H-tiles
    (1, 13, 4, 5, 8, dict(pad=2, oh_blk=3, c_blk=4, m_blk=8)),
    # one full-height tile taller than the image (VGG conv5: H 14,
    # hp_blk 16), two images a step
    (2, 14, 8, 3, 8, dict(pad=1, oh_blk=14, b_blk=2, c_blk=8, m_blk=8)),
    # columns out to g.w 34 for W 28, wider than the pad (VGG conv4)
    (1, 28, 8, 3, 8, dict(pad=1, oh_blk=8, c_blk=8, m_blk=8)),
    # the row- and column-folded layers: C 3 (conv1_1) and C 64 (conv1_2)
    (2, 12, 3, 3, 16, dict(pad=1, oh_blk=4, c_blk=27, m_blk=16)),
    (1, 10, 64, 3, 16, dict(pad=1, oh_blk=4, c_blk=576, m_blk=16)),
    # a fused 3x3/2 pool over H-tiles
    (1, 17, 8, 3, 8, dict(pad=1, pool="max", pool_k=3, pool_s=2,
                          oh_blk=4, c_blk=8, m_blk=8)),
    # AlexNet conv2's layout: two groups of 48, 5x5 taps, pad 2
    (2, 9, 96, 5, 16, dict(pad=2, groups=2, c_blk=240, m_blk=8)),
    # two C-tiles and two M-tiles: the tile is made again per C-tile;
    # b_blk 2 does not divide a batch of 3
    (3, 9, 96, 3, 16, dict(pad=1, oh_blk=4, b_blk=2, c_blk=48, m_blk=8)),
], ids=["pad1_tiles", "pad2_tiles", "full_height", "right_overhang",
        "fold_c3", "fold_c64", "pool", "grouped_pad2", "c_tiles"])
def test_halo_in_kernel_matches_oracle(B, H, C, K, M, kw):
    """The kernel makes the pad rows and columns, the columns out to
    ``ow_p + kw - 1`` and the last tile's rows in VMEM: the oracle's
    answer, and the wrapper-padded kernel's sums bit for bit."""
    assert _halo_engaged(B, H, C, K, M, **kw)
    _check(B, H, C, K, M, **kw)
    x = _rand((B, H, H, C))
    w = _rand((K, K, C // kw.get("groups", 1), M), scale=0.2)
    b = _rand((M,))
    got = conv_pipe(x, w, b, **kw)
    padded = conv_pipe(x, w, b, **kw, vmem_budget=0)   # the wrapper's pad
    np.testing.assert_array_equal(np.asarray(got), np.asarray(padded))


def test_halo_in_kernel_int8_bit_equal():
    """int8 tiles take the halo made in VMEM too: AlexNet conv2's grouped
    pad-2 layout with its pool, code for code with the exact-int
    reference."""
    x = _rand((3, 11, 11, 96))
    w = _rand((5, 5, 48, 16), scale=0.2)
    b = _rand((16,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    xq = quantize(x, sx)
    kw = dict(pad=2, groups=2, pool="max", pool_k=3, pool_s=2)
    blocks = dict(c_blk=240, m_blk=8, oh_blk=4, b_blk=2)
    assert _halo_engaged(3, 11, 96, 5, 16, dtype="int8", **kw, **blocks)
    got = conv_pipe(xq, wq, b, scale=ws * sx, out_scale=0.05, **blocks,
                    **kw)
    want = qref.conv_int8_ref(xq, wq, b, ws * sx, out_scale=0.05, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_halo_follows_the_vmem_budget():
    """A plan whose working set with the line buffer would pass its
    budget keeps the wrapper's pad, so every plan fits a budget exactly
    when it did before: VGG-16 conv4_2 at its tuned batch-32 plan."""
    s = autotune.ConvShape(h=28, w=28, c=512, kh=3, kw=3, m=512, pad=1,
                           b=32)
    plan = (256, 256, 4, 8)
    assert not autotune.halo_in_kernel(s, *plan)
    assert autotune.conv_vmem_bytes(s, *plan) == 16531456
    assert autotune.halo_in_kernel(s, *plan, vmem_budget=32 * 2 ** 20)
    assert autotune.conv_vmem_bytes(s, *plan, 32 * 2 ** 20) > 16531456
    conv1_2 = autotune.ConvShape(h=224, w=224, c=64, kh=3, kw=3, m=64,
                                 pad=1, pool="max", b=32)
    assert autotune.halo_in_kernel(conv1_2, 576, 64, 4, 1)
    assert not autotune.halo_in_kernel(
        autotune.ConvShape(h=227, w=227, c=3, kh=11, kw=11, m=96,
                           stride=4, b=32), 432, 96, 8, 2)


def test_stride1_conv_no_pad_outside_kernel():
    """Acceptance: a stride-1 conv's input reaches the ``pallas_call``
    unpadded — no ``pad`` primitive in the wrapper."""
    x = _rand((1, 15, 15, 8))
    w = _rand((3, 3, 8, 16), scale=0.2)
    b = _rand((16,))
    jaxpr = jax.make_jaxpr(
        lambda x, w, b: ops.fused_conv(
            x, w, b, pad=1, pool="max", pool_k=2, pool_s=2,
            use_pallas=True, oh_blk=4))(x, w, b).jaxpr
    prims = _prims_outside_kernels(jaxpr)
    assert prims.count("pallas_call") == 1
    assert "pad" not in prims


# ---------------------------------------------------------------------------
# the pooled epilogue over 128-lane slabs (VGG-16's wide conv->pool tiles)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,m_blk", [
    (512, 256),     # two M-tiles of two slabs (VGG conv3_3, conv4_3)
    (512, 512),     # one M-tile of four slabs (VGG conv5_3)
    (64, 64),       # one slab narrower than a lane tile (VGG conv1_2)
])
def test_pool_slabs_match_oracle(M, m_blk):
    """A pooled tile wider than a lane tile is pooled one 128-lane slab
    at a time and rejoined in lanes; pool windows straddle H-tiles."""
    _check(2, 10, 8, 3, M, pad=1, pool="max", pool_k=2, pool_s=2,
           oh_blk=4, b_blk=2, c_blk=8, m_blk=m_blk)


@pytest.mark.parametrize("M,m_blk", [(512, 256), (512, 512), (64, 64)])
def test_pool_slabs_int8_bit_equal(M, m_blk):
    """The int8 pipeline runs the same slab epilogue (requantize, bias,
    ReLU, pool over slabs, round): code for code with the exact-int
    reference."""
    x = _rand((2, 10, 10, 8))
    w = _rand((3, 3, 8, M), scale=0.2)
    b = _rand((M,), scale=0.1)
    sx = float(abs_max_scale(x))
    wq, ws = quantize_channelwise(w, axis=-1)
    xq = quantize(x, sx)
    kw = dict(pad=1, pool="max", pool_k=2, pool_s=2, out_scale=0.05)
    got = conv_pipe(xq, wq, b, scale=ws * sx, c_blk=8, m_blk=m_blk,
                    oh_blk=4, b_blk=2, **kw)
    want = qref.conv_int8_ref(xq, wq, b, ws * sx, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# batch folding (the serving path): b_blk images per grid step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,b_blk", [
    (1, 1),       # degenerate: single image
    (4, 2),       # dividing block
    (5, 2),       # non-dividing: trailing partial block is zero-padded
    (3, 8),       # block larger than the batch (clamped)
    (6, 0),       # 0 = whole batch in one block
])
def test_batch_fold_equivalence(B, b_blk):
    """The batch-folded grid matches per-image results for every (B, b_blk),
    including batches the block size does not divide."""
    _check(B, 12, 4, 3, 8, pad=1, oh_blk=4, b_blk=b_blk)


@pytest.mark.parametrize("b_blk", [1, 2, 3])
def test_batch_fold_grouped_conv(b_blk):
    """Batch folding composes with in-kernel grouped conv (AlexNet towers):
    the x index map must offset both the image block and the group slab."""
    _check(5, 15, 8, 5, 16, pad=2, pool="max", pool_k=3, pool_s=2,
           oh_blk=4, b_blk=b_blk, groups=2)


@pytest.mark.parametrize("b_blk", [2, 4])
def test_batch_fold_straddling_pool_windows(b_blk):
    """Overlapping pool windows (pool_k > pool_s) straddle H-tile
    boundaries; the halo recompute must stay per-image under folding."""
    _check(6, 17, 4, 3, 8, pad=1, pool="max", pool_k=3, pool_s=2,
           oh_blk=4, b_blk=b_blk)


def test_batch_fold_strided(b_blk=3):
    _check(7, 23, 3, 5, 8, stride=2, pad=2, oh_blk=4, b_blk=b_blk)


def test_batch_fold_single_pallas_call():
    """Acceptance: a batch-8 fused conv is ONE pallas_call (the batch is
    folded into the grid, not looped over in Python)."""
    x = _rand((8, 12, 12, 4))
    w = _rand((3, 3, 4, 8), scale=0.2)
    b = _rand((8,))
    jaxpr = str(jax.make_jaxpr(
        lambda x, w, b: ops.fused_conv(
            x, w, b, pad=1, pool="max", use_pallas=True, oh_blk=4,
            b_blk=4))(x, w, b))
    assert jaxpr.count("pallas_call") == 1


def test_batched_plan_no_slower_at_batch4():
    """Acceptance: the jointly-tuned folded plan models no slower than the
    best per-image plan at batch >= 4, and strictly faster on a
    weight-traffic-bound layer (AlexNet conv3 geometry)."""
    for b in (4, 8):
        s = autotune.ConvShape(h=13, w=13, c=256, kh=3, kw=3, m=384,
                               pad=1, b=b)
        plans = autotune.enumerate_plans(s)
        folded = autotune.best_plan(s)
        per_image = min((p for p in plans if p.b_blk == 1),
                        key=lambda p: p.t_model)
        assert folded.t_model <= per_image.t_model
        assert folded.b_blk > 1          # the fold is actually chosen
        assert folded.t_model < per_image.t_model  # and it wins outright


def test_batched_vmem_model_scales_with_b_blk():
    """x tile, out tile and accumulator scale with b_blk; the weight tile
    does not — the VMEM model must reflect the fold's asymmetry."""
    s = autotune.ConvShape(h=16, w=16, c=16, kh=3, kw=3, m=32, pad=1, b=8)
    v1 = autotune.conv_vmem_bytes(s, 8, 16, 4, 1)
    v4 = autotune.conv_vmem_bytes(s, 8, 16, 4, 4)
    w_tile = 2 * 3 * 3 * 8 * 16 * 4          # double-buffered w bytes
    assert v4 > v1
    assert v4 - w_tile < 4 * v1              # sub-linear: w doesn't scale


def test_plan_registry_keyed_by_batch():
    """Serving batch is part of the plan-cache key: tuning the same layer
    at b=1 and b=8 yields two registry entries (and possibly different
    b_blk picks)."""
    autotune.clear_registry()
    base = dict(h=14, w=14, c=32, kh=3, kw=3, m=64, pad=1)
    autotune.get_plan(autotune.ConvShape(**base))
    autotune.get_plan(autotune.ConvShape(**base, b=8))
    assert len(autotune.registry_snapshot()) == 2
    autotune.clear_registry()


def test_cnn_forward_batched_matches_ref(forward):
    """Whole-model check at a serving batch the plans don't divide: the
    autotuned pallas path (batch in the plan key) vs the XLA reference."""
    from repro.models.cnn import init_cnn_params
    cfg = get_config("vgg16").smoke()
    params = init_cnn_params(KEY, cfg)
    x = _rand((5, cfg.input_hw, cfg.input_hw, cfg.input_ch))
    y_ref = forward(params, x, cfg, use_pallas=False)
    y_pal = forward(params, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# tile geometry model
# ---------------------------------------------------------------------------

def test_tile_geometry_covers_output_exactly():
    for oh in (7, 16, 55):
        for oh_blk in (1, 2, 4, 5, oh):
            for pool, pk, ps in ((None, 2, 2), ("max", 3, 2), ("max", 2, 2)):
                if pool and oh <= pk:
                    continue
                n_h, pr, oh_ext, hp_blk, row_step = conv_tile_geometry(
                    oh, oh_blk, kh=3, pool=pool, pool_k=pk, pool_s=ps)
                out_rows = (oh - pk) // ps + 1 if pool else oh
                assert n_h * pr >= out_rows          # tiles cover the output
                assert (n_h - 1) * pr < out_rows     # last tile is needed
                # a tile's conv rows span all rows its pool windows read
                assert oh_ext >= (pr - 1) * (ps if pool else 1) + \
                    (pk if pool else 1)
                assert hp_blk == (oh_ext - 1) * 1 + 3


# ---------------------------------------------------------------------------
# autotuner: VMEM budget + plan behaviour
# ---------------------------------------------------------------------------

def _conv_shapes(cfg):
    """(ConvShape, layer) for every conv in a CNNConfig, with fused pool."""
    h = cfg.input_hw
    c = cfg.input_ch
    out = []
    layers = cfg.layers
    for i, l in enumerate(layers):
        if l.kind == "conv":
            nxt = layers[i + 1] if i + 1 < len(layers) else None
            pool = nxt if nxt is not None and nxt.kind == "pool" else None
            out.append(autotune.ConvShape(
                h=h, w=h, c=c, kh=l.kernel, kw=l.kernel, m=l.out_ch,
                stride=l.stride, pad=l.pad, groups=l.groups,
                pool=(pool.pool if pool else None),
                pool_k=(pool.kernel if pool else 2),
                pool_s=(pool.stride if pool else 2), dtype=cfg.dtype))
            h = (h + 2 * l.pad - l.kernel) // l.stride + 1
            c = l.out_ch
        elif l.kind == "pool":
            h = (h - l.kernel) // l.stride + 1
    return out


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_every_paper_layer_fits_vmem_budget(name):
    """Acceptance: the VMEM-footprint model shows every AlexNet and VGG-16
    conv layer fits a 16 MiB budget under the autotuned plan. (The seed's
    full-height kernel needed ~13 MiB for the ACCUMULATOR ALONE on VGG
    conv1-2 and could not schedule.)"""
    budget = 16 * 2 ** 20
    shapes = _conv_shapes(get_config(name))
    assert shapes, "config must contain conv layers"
    for s in shapes:
        plan = autotune.get_plan(s, vmem_budget=budget)
        assert plan.vmem_bytes <= budget, (s, plan)
        # and the model agrees when recomputed from the knobs
        assert autotune.conv_vmem_bytes(
            s, plan.c_blk, plan.m_blk, plan.oh_blk) == plan.vmem_bytes


def test_seed_full_height_plan_busts_vmem_on_vgg_conv2():
    """The motivating failure: full-height VGG conv2 (224x224x64 -> 64)
    does NOT fit 16 MiB, which is why H-tiling exists."""
    s = autotune.ConvShape(h=224, w=224, c=64, kh=3, kw=3, m=64, pad=1)
    full = autotune.conv_vmem_bytes(s, 8, 32, 0)     # seed knobs, full H
    assert full > 16 * 2 ** 20
    tuned = autotune.get_plan(s)
    assert tuned.vmem_bytes <= 16 * 2 ** 20
    assert tuned.oh_blk < s.oh                       # it actually tiled


def test_plan_registry_memoises():
    autotune.clear_registry()
    s = autotune.ConvShape(h=32, w=32, c=16, kh=3, kw=3, m=32, pad=1)
    p1 = autotune.get_plan(s)
    p2 = autotune.get_plan(s)
    assert p1 is p2
    assert len(autotune.registry_snapshot()) == 1
    # a different dtype is a different registry entry
    s2 = autotune.ConvShape(h=32, w=32, c=16, kh=3, kw=3, m=32, pad=1,
                            dtype="bfloat16")
    autotune.get_plan(s2)
    assert len(autotune.registry_snapshot()) == 2
    autotune.clear_registry()


def test_tuned_plan_runs_and_matches_oracle():
    """End to end: tune a smoke-scale layer, run conv_pipe with the plan."""
    s = autotune.ConvShape(h=19, w=19, c=6, kh=3, kw=3, m=16, pad=1,
                           pool="max", pool_k=3, pool_s=2)
    plan = autotune.best_plan(s, vmem_budget=768 * 1024)  # force tiling
    assert plan.vmem_bytes <= 768 * 1024
    _check(1, 19, 6, 3, 16, pad=1, pool="max", pool_k=3, pool_s=2,
           oh_blk=plan.oh_blk, c_blk=plan.c_blk, m_blk=plan.m_blk)


def test_cnn_forward_autotuned_matches_ref(forward):
    """The full model path with autotuned plans (use_pallas) vs XLA ref."""
    from repro.models.cnn import init_cnn_params
    cfg = get_config("vgg16").smoke()
    params = init_cnn_params(KEY, cfg)
    x = _rand((1, cfg.input_hw, cfg.input_hw, cfg.input_ch))
    y_ref = forward(params, x, cfg, use_pallas=False)
    y_pal = forward(params, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=5e-4, atol=5e-4)


def test_grouped_cnn_forward_alexnet_smoke(forward):
    """AlexNet smoke through the pallas path exercises in-kernel groups."""
    from repro.models.cnn import init_cnn_params
    cfg = get_config("alexnet").smoke()
    params = init_cnn_params(KEY, cfg)
    x = _rand((1, cfg.input_hw, cfg.input_hw, cfg.input_ch))
    y_ref = forward(params, x, cfg, use_pallas=False)
    y_pal = forward(params, x, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=5e-2, atol=5e-2)   # PWL LRN tolerance
