"""conv_pipe — the PipeCNN pipeline as ONE fused, spatially-tiled Pallas
TPU kernel.

PipeCNN cascades MemRD -> Conv -> Pool -> MemWR through OpenCL channels so
inter-stage data never touches DDR. On TPU the same dataflow is one
`pallas_call`:

  * The BlockSpec index maps ARE the data movers (MemRD/MemWR): they drive
    the HBM->VMEM DMA engine with the Fig. 4 work-item mapping.
  * The conv is computed as an on-the-fly im2col matmul on the MXU
    (kh/kw-unrolled stride-1 slices — the multi-mode engine's conv mode;
    a strided conv is space-to-depth'd in the wrapper first).
  * bias + ReLU + line-buffer pooling run in the epilogue while the tile is
    still in VMEM (the Conv->Pool channel).

Grid: ``(B_tiles * H_tiles, M_tiles, C_tiles)`` with the input-channel axis
LAST and "arbitrary" semantics — the fp32 VMEM scratch accumulates partial
sums across C-tiles (the paper's delayed-buffer accumulator; the MXU needs
no II=2 shift register).

Batch pipelining (the serving path): the batch axis is FOLDED into the
leading grid axis rather than being its own axis — each grid step processes
a ``b_blk``-image block of one H-tile, so a small-image batch streams
through ONE ``pallas_call`` whose leading axis has ``ceil(B/b_blk) *
H_tiles`` steps. ``b_blk > 1`` is the paper's batched-FC argument applied
to conv: the weight tile fetched for a grid step amortizes over ``b_blk``
images, and the im2col matmul's row dimension grows to ``b_blk * oh_ext *
OW``, filling the MXU when single-image tiles would under-fill it. The x
index map decomposes the folded axis (``bh // n_h`` selects the image
block, ``bh % n_h`` the H-tile) so halo reads stay per-image.

Spatial tiling (the FPGA line buffer): each grid step DMAs only the
``(oh_ext - 1) * stride + KH`` input rows its output-row tile needs. The
input tiles OVERLAP by the halo rows (``KH - stride`` per conv step plus
``pool_k - pool_s`` recomputed conv rows per pool step), which standard
blocked BlockSpecs cannot express, so the x spec indexes H by element
(``pl.Element``): its index map returns that row offset directly. The
fp32 accumulator shrinks from (OH, OW, m_blk) to (oh_ext, OW, m_blk), which is what lets
paper-scale layers (VGG-16 conv1: 224x224x64) fit a 16 MiB VMEM budget.

Grouped convolution (AlexNet's two towers) is folded into the grid: groups
get a leading array axis of their own, the M-tile axis spans all groups'
output tiles and the index maps select the owning group — one
``pallas_call``, no per-group Python loop, and a per-group slab narrower
than 128 lanes (AlexNet conv2: 48 channels) is a whole-dim block.

Fixed-point mode (the paper's headline resource trade): pass int8 ``x``/``w``
with a per-output-channel ``scale`` vector (= s_x * s_w[m]) and the kernel
runs the PipeCNN fixed-point pipeline — int8 tiles DMA'd (4x less HBM
traffic than fp32), int32 MXU accumulation, and a fused requantize ->
bias -> ReLU -> pool epilogue. A static ``out_scale`` requantizes the
result to int8 for the next layer (calibrated offline); ``out_scale=None``
emits fp32 (the classifier's logits).

Block-size knobs map to the paper's throughput parameters:
  C_BLK  <-> VEC_SIZE     (input-feature vectorization)
  M_BLK  <-> CU_NUM       (parallel output-feature CUs)
  OH_BLK <-> line-buffer depth (rows resident on chip)
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

LANE = 128          # VMEM lane width: the minor block dim is a multiple of it
SUBLANE = 8         # fp32 sublane count: the im2col row axis is padded to it


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lane_legal(blk: int, full: int) -> bool:
    """Mosaic's rule for a minor (lane) block dim: a multiple of 128 or the
    whole array dim."""
    return blk >= full or blk % LANE == 0


class S2DGeometry(NamedTuple):
    """The stride-1 conv that ``conv_pipe`` actually runs.

    A stride-``s`` conv is rewritten by space-to-depth in the wrapper: the
    (padded) input's s x s pixel blocks fold into channels, so the kernel
    only ever takes stride-1 taps (Mosaic refuses strided value slices).
    AlexNet conv1 (227x227x3, 11x11/4) becomes 57x57x48 with 3x3 taps.
    """
    h: int          # input rows after padding and space-to-depth
    w: int          # input cols the kernel reads (>= ow_p + kw - 1)
    c: int          # channels per group after space-to-depth (s*s*cg)
    kh: int         # taps per axis after space-to-depth (ceil(K / s))
    kw: int
    oh: int         # true conv output rows / cols
    ow: int
    ow_p: int       # conv cols computed per row (ow rounded up to SUBLANE)


def s2d_geometry(h: int, w: int, cg: int, kh: int, kw: int, *,
                 stride: int, pad: int) -> S2DGeometry:
    """Resolve the stride-1 geometry shared by the kernel and the tuner."""
    hp, wp = h + 2 * pad, w + 2 * pad
    oh = (hp - kh) // stride + 1
    ow = (wp - kw) // stride + 1
    s = stride
    khs, kws = -(-kh // s), -(-kw // s)
    ow_p = _round_up(ow, SUBLANE)
    return S2DGeometry(h=-(-hp // s), w=max(-(-wp // s), ow_p + kws - 1),
                       c=s * s * cg, kh=khs, kw=kws, oh=oh, ow=ow,
                       ow_p=ow_p)


def conv_tile_geometry(oh: int, oh_blk: int, *, stride: int, kh: int,
                       pool: Optional[str], pool_k: int, pool_s: int
                       ) -> Tuple[int, int, int, int, int]:
    """Resolve the H-tiling geometry shared by kernel, tuner and tests.

    Returns ``(n_h, pr, oh_ext, hp_blk, row_step)``:
      n_h      number of H-tiles in the grid
      pr       final-output rows produced per tile (pooled rows if pooling)
      oh_ext   conv rows computed per tile (pr*pool_s span + pool_k window)
      hp_blk   input rows DMA'd per tile (the line-buffer depth)
      row_step input-row element offset between consecutive tiles

    ``oh_blk`` counts conv-output rows per tile; 0 means "full height".
    With pooling it is rounded up to a multiple of ``pool_s`` so every pool
    window is computed by exactly one tile (windows that straddle the tile
    boundary are handled by recomputing ``pool_k - pool_s`` conv rows).
    The kernel calls it with the space-to-depth taps (``stride=1``,
    ``kh`` = :class:`S2DGeometry` ``kh``).
    """
    oh_blk = min(oh_blk, oh) if oh_blk else oh
    oh_blk = max(1, oh_blk)
    if pool is not None:
        oh_blk = _round_up(oh_blk, pool_s)
        ph = (oh - pool_k) // pool_s + 1
        pr = oh_blk // pool_s
        n_h = -(-ph // pr)
        oh_ext = (pr - 1) * pool_s + pool_k
    else:
        pr = oh_blk
        n_h = -(-oh // oh_blk)
        oh_ext = oh_blk
    hp_blk = (oh_ext - 1) * stride + kh
    row_step = oh_blk * stride
    return n_h, pr, oh_ext, hp_blk, row_step


def _space_to_depth(x: jax.Array, w: jax.Array, s: int, g: S2DGeometry
                    ) -> Tuple[jax.Array, jax.Array]:
    """Fold s x s pixel blocks into channels: x (G,B,H,W,C) -> (G,B,H/s,
    W/s,s*s*C) and w (G,KH,KW,C,M) -> (G,KH/s,KW/s,s*s*C,M), with zero
    taps padding K up to a multiple of s. Channel order is (row phase,
    col phase, c) on both sides."""
    G, B, H, W, C = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, g.h * s - H), (0, -(-W // s) * s - W),
                    (0, 0)))
    x = x.reshape(G, B, g.h, s, -1, s, C).transpose(0, 1, 2, 4, 3, 5, 6)
    x = x.reshape(G, B, g.h, -1, s * s * C)
    _, KH, KW, _, M = w.shape
    w = jnp.pad(w, ((0, 0), (0, g.kh * s - KH), (0, g.kw * s - KW), (0, 0),
                    (0, 0)))
    w = w.reshape(G, g.kh, s, g.kw, s, C, M).transpose(0, 1, 3, 2, 4, 5, 6)
    return x, w.reshape(G, g.kh, g.kw, s * s * C, M)


def _conv_pipe_kernel(x_ref, w_ref, b_ref, *refs, oh_ext: int, ow: int,
                      ow_p: int, relu: bool, pool: Optional[str],
                      pool_k: int, pool_s: int, pr: int, n_c_tiles: int,
                      quantized: bool = False,
                      out_scale: Optional[float] = None):
    """One (B-block, H-tile, M-tile) output block; accumulates over C-tiles.

    ``quantized`` inserts the per-channel scale ref after the bias and
    switches the accumulator to int32 (the fixed-point pipeline); the
    epilogue then requantizes (scale -> bias -> ReLU -> pool -> round).
    """
    if quantized:
        s_ref, refs = refs[0], refs[1:]
    if pool is not None:
        o_ref, acc_ref, y_ref = refs
    else:
        o_ref, acc_ref = refs
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b_blk, _, _, c_blk = x_ref.shape               # (B_BLK, HP_BLK, WP, C)
    kh, kw, _, m_blk = w_ref.shape                 # (KH, KW, C_BLK, M_BLK)
    rows = b_blk * oh_ext * ow_p
    acc_t = jnp.int32 if quantized else jnp.float32
    # fp32 is computed at fp32 on the MXU (the default would round the
    # operands to bf16); int8 x int8 -> int32 is exact either way
    prec = None if quantized else jax.lax.Precision.HIGHEST

    # on-the-fly im2col: kh*kw stride-1 taps (space-to-depth removed the
    # conv stride in the wrapper), each a (B_BLK*OH_EXT*OW_P, C) x (C, M)
    # matmul on the MXU, accumulated in VMEM scratch (fp32, or exact int32
    # in fixed-point mode). The batch block rides in the row dimension,
    # so one weight fetch feeds b_blk images (batched weight reuse).
    # OW_P is a multiple of 8, so folding (B, OH, OW_P) into the MXU row
    # axis is a free relayout; the tail columns are dropped below.
    for j in range(kw):
        xj = x_ref[:, :, pl.ds(j, ow_p), :]        # (B_BLK, HP_BLK, OW_P, C)
        for i in range(kh):
            patch = xj[:, i:i + oh_ext].reshape(rows, c_blk)
            acc_ref[...] += jax.lax.dot_general(
                patch, w_ref[i, j], (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=acc_t)

    @pl.when(c_idx == n_c_tiles - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32)
        if quantized:
            # requantize: int32 accumulator x (s_x * s_w[m]), THEN bias —
            # bias stays fp32 so it needs no per-channel rescaling
            y = y * s_ref[...]
        y = y + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        y = y.reshape(b_blk, oh_ext, ow_p, m_blk)
        if pool is not None:
            # line-buffer pooling: the conv tile is still in VMEM; reduce
            # pool_k x pool_k windows read back with strided loads (the
            # (L+1)-input pool logic). oh_ext was sized so every window
            # lies inside this tile.
            y_ref[...] = y
            pw = o_ref.shape[2]
            win = None
            for i in range(pool_k):
                for j in range(pool_k):
                    sl = y_ref[:, pl.ds(i, pr, stride=pool_s),
                               pl.ds(j, pw, stride=pool_s), :]
                    if win is None:
                        win = sl
                    elif pool == "max":
                        win = jnp.maximum(win, sl)
                    else:
                        win = win + sl
            y = win / (pool_k * pool_k) if pool == "avg" else win
        else:
            y = y[:, :, :ow]
        if quantized and out_scale is not None:
            # emit int8 for the next layer: same round-half-even/clip as
            # quant.core.quantize, so kernel and reference are bit-equal
            y = jnp.clip(jnp.round(y / out_scale), -127, 127)
        o_ref[...] = y.astype(o_ref.dtype)


def conv_pipe(x: jax.Array, w: jax.Array, b: jax.Array, *,
              scale: Optional[jax.Array] = None,
              out_scale: Optional[float] = None,
              stride: int = 1, pad: int = 0, relu: bool = True,
              pool: Optional[str] = None, pool_k: int = 2, pool_s: int = 2,
              c_blk: int = LANE, m_blk: int = LANE, oh_blk: int = 0,
              b_blk: int = 1, groups: int = 1,
              interpret: Optional[bool] = None) -> jax.Array:
    """Fused conv(+bias)(+ReLU)(+pool). x (B,H,W,C); w (KH,KW,C/G,M); b (M,).

    c_blk/m_blk are the VEC_SIZE/CU_NUM analogues, counted in the
    space-to-depth channels (``s*s*C/G``) and per-group output channels;
    oh_blk is the line-buffer depth in conv-output rows (0 = full height);
    b_blk is the number of images per grid step (0 = whole batch).
    ``groups`` runs grouped convolution inside the one kernel: groups get
    an array axis of their own, so a narrow per-group slab (AlexNet conv2:
    48 channels) is a whole-dim block rather than a misaligned lane window.
    ``interpret`` (None = from the backend, :mod:`repro.kernels.mode`)
    runs the kernel body on the host; compiled, c_blk/m_blk must be
    lane-legal (:func:`lane_legal`).

    Fixed-point mode: ``scale`` (fp32, (M,), = s_x * s_w per output channel)
    switches to the int8 pipeline — x/w must be int8, accumulation is
    int32, and the epilogue requantizes. ``out_scale`` (a static python
    float) selects int8 output quantized by that step; None emits fp32.
    Zero padding (halo / channel / batch / space-to-depth) is exact
    because the scheme is symmetric (zero-point 0).
    """
    interpret = resolve_interpret(interpret)
    B, H, W, C = x.shape
    quantized = scale is not None
    KH, KW, _, M = w.shape
    if C % groups or M % groups:
        raise ValueError(f"groups={groups} must divide C={C} and M={M}")
    cg, mg = C // groups, M // groups
    if w.shape[2] != cg:
        raise ValueError(f"w channel axis {w.shape[2]} != C/groups = {cg}")
    g = s2d_geometry(H, W, cg, KH, KW, stride=stride, pad=pad)
    if pool is not None:
        ph = (g.oh - pool_k) // pool_s + 1
        pw = (g.ow - pool_k) // pool_s + 1
    else:
        ph, pw = g.oh, g.ow

    # groups -> a leading array axis: x (G,B,H,W,cg), w (G,KH,KW,cg,mg)
    x = x.reshape(B, H, W, groups, cg).transpose(3, 0, 1, 2, 4)
    w = w.reshape(KH, KW, cg, groups, mg).transpose(3, 0, 1, 2, 4)
    x = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad), (0, 0)))
    if stride > 1:
        x, w = _space_to_depth(x, w, stride, g)
    # right-pad W so every tap reads ow_p columns in bounds
    x = jnp.pad(x, ((0, 0),) * 3 + ((0, g.w - x.shape[3]), (0, 0)))

    c_blk = min(c_blk, g.c)
    m_blk = min(m_blk, mg)
    if not interpret and not (lane_legal(c_blk, g.c)
                              and lane_legal(m_blk, mg)):
        raise ValueError(
            f"c_blk={c_blk}/m_blk={m_blk} cannot lower: a lane block must "
            f"be a multiple of {LANE} or the whole per-group dim "
            f"({g.c}/{mg})")
    # pad channels PER GROUP so group slabs stay c_blk/m_blk aligned
    cgp, mgp = _round_up(g.c, c_blk), _round_up(mg, m_blk)
    x = jnp.pad(x, ((0, 0),) * 4 + ((0, cgp - g.c),))
    w = jnp.pad(w, ((0, 0),) * 3 + ((0, cgp - g.c), (0, mgp - mg)))
    b = jnp.pad(b.reshape(groups, 1, mg), ((0, 0), (0, 0), (0, mgp - mg)))
    n_c, n_mg = cgp // c_blk, mgp // m_blk
    n_m = groups * n_mg

    n_h, pr, oh_ext, hp_blk, row_step = conv_tile_geometry(
        g.oh, oh_blk, stride=1, kh=g.kh,
        pool=pool, pool_k=pool_k, pool_s=pool_s)

    # batch folding: b_blk images share each grid step (0 = whole batch);
    # pad B up so the image-block axis tiles evenly (zero images, dropped)
    b_blk = min(b_blk, B) if b_blk else B
    b_blk = max(1, b_blk)
    n_b = -(-B // b_blk)
    # bottom-pad the input so the last tile's halo read stays in bounds
    # (its surplus conv rows are garbage-from-zeros, sliced off below)
    need_h = (n_h - 1) * row_step + hp_blk
    x = jnp.pad(x, ((0, 0), (0, n_b * b_blk - B),
                    (0, max(0, need_h - x.shape[2])), (0, 0), (0, 0)))

    kernel = functools.partial(
        _conv_pipe_kernel, oh_ext=oh_ext, ow=g.ow, ow_p=g.ow_p, relu=relu,
        pool=pool, pool_k=pool_k, pool_s=pool_s, pr=pr, n_c_tiles=n_c,
        quantized=quantized, out_scale=out_scale)

    # x tiles overlap by the halo rows => element-offset indexing on H
    # only (B, W and C stay blocked); the folded leading axis decomposes
    # into (image block, H-tile); the group of M-tile mi selects the
    # group axis of x and w.
    # Mosaic takes element offsets on every dim or on none, so B, W and C
    # return block-aligned offsets (a literal 0 where the block is the
    # whole dim, so the lane offset is provably tile-aligned).
    x_spec = pl.BlockSpec(
        (None, pl.Element(b_blk), pl.Element(hp_blk), pl.Element(g.w),
         pl.Element(c_blk)),
        lambda bh, mi, ci: (mi // n_mg, (bh // n_h) * b_blk,
                            (bh % n_h) * row_step, 0,
                            ci * c_blk if n_c > 1 else 0))
    # bias and the requantize multiplier ride as (1, M_BLK) lane rows
    vec_spec = pl.BlockSpec((None, 1, m_blk),
                            lambda bh, mi, ci: (mi // n_mg, 0, mi % n_mg))
    in_specs = [
        x_spec,
        pl.BlockSpec((None, g.kh, g.kw, c_blk, m_blk),
                     lambda bh, mi, ci: (mi // n_mg, 0, 0, ci, mi % n_mg)),
        vec_spec,
    ]
    args = [x, w, b]
    if quantized:
        in_specs.append(vec_spec)
        args.append(jnp.pad(scale.astype(jnp.float32).reshape(groups, 1, mg),
                            ((0, 0), (0, 0), (0, mgp - mg))))
    out_spec = pl.BlockSpec(
        (None, b_blk, pr, pw, m_blk),
        lambda bh, mi, ci: (mi // n_mg, bh // n_h, bh % n_h, 0, mi % n_mg))
    if quantized:
        out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    else:
        out_dtype = x.dtype
    out_shape = jax.ShapeDtypeStruct(
        (groups, n_b * b_blk, n_h * pr, pw, mgp), out_dtype)

    acc_dtype = jnp.int32 if quantized else jnp.float32
    scratch = [pltpu.VMEM((b_blk * oh_ext * g.ow_p, m_blk), acc_dtype)]
    if pool is not None:
        scratch.append(pltpu.VMEM((b_blk, oh_ext, g.ow_p, m_blk),
                                  jnp.float32))

    out = pl.pallas_call(
        kernel,
        grid=(n_b * n_h, n_m, n_c),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    out = out[:, :B, :ph, :, :mg]                  # (G, B, PH, PW, mg)
    return out.transpose(1, 2, 3, 0, 4).reshape(B, ph, pw, M)
