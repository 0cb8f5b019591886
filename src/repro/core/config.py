"""Configuration system for the PipeCNN-on-TPU framework.

Two config families:
  * :class:`ModelConfig` — the LM-family architectures (dense / MoE / SSM /
    hybrid / VLM / audio backbones) that the framework must support.
  * :class:`CNNConfig` — the paper's own CNN models (AlexNet, VGG-16).

Every assigned architecture lives in ``repro/configs/<id>.py`` as a module
exposing ``CONFIG``; ``repro.configs.get_config(name)`` resolves them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# LM-family model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm", "audio")


class SpecError(ValueError):
    """A config/spec field failed cross-validation.

    Carries the dotted name of the offending field (``.field``, e.g.
    ``"Precision.quant"`` or ``"CNNConfig.pp_stages"``) so constructor
    rejections and ``repro.analysis`` verifier findings can name the
    same knob with the same words. Subclasses ``ValueError`` so every
    pre-existing ``except ValueError`` / ``pytest.raises(ValueError)``
    site keeps working.
    """

    def __init__(self, field: str, message: str):
        self.field = field
        super().__init__(message)


@dataclass(frozen=True)
class ModelConfig:
    """Unified configuration for every supported LM-family architecture."""

    name: str
    family: str                       # one of FAMILIES
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                         # dense FFN width (0 => no FFN, e.g. xLSTM)
    vocab: int

    # --- attention details ---
    d_head: int = 0                   # 0 => d_model // n_heads
    qk_norm: bool = False             # RMSNorm on q/k per head (Qwen3)
    rope_theta: float = 1e6
    norm_eps: float = 1e-6

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_dense_residual: bool = False  # Arctic: dense FFN in parallel with MoE
    # dispatch groups: tokens are grouped (group dim sharded over the data
    # axis) and expert capacity is enforced PER GROUP, so the dispatch
    # scatter/gather never crosses data shards (§Perf MoE iteration 2).
    moe_groups: int = 1

    # --- SSM (Mamba2) ---
    ssm_state: int = 0                # d_state
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 128              # chunk length for the chunked scan
    ssm_conv_width: int = 4

    # --- hybrid (Zamba2): shared attention block applied every k SSM blocks
    attn_every: int = 0               # 0 => no interleaved attention

    # --- xLSTM: alternate mLSTM / sLSTM blocks (1:1)
    xlstm_slstm_every: int = 2        # every 2nd block is an sLSTM

    # --- modality frontend stubs (assignment: backbone only) ---
    frontend: Optional[str] = None    # "patch_embed" (vlm) | "frame_embed" (audio)
    frontend_len: int = 0             # number of precomputed embedding positions

    # --- numerics / memory ---
    dtype: str = "bfloat16"           # activation/param compute dtype
    opt_state_dtype: str = "float32"  # AdamW m/v dtype (bf16 for very large models)
    remat: bool = True                # activation checkpointing over blocks
    remat_policy: str = "full"        # "full" | "dots" (save dot outputs)

    # --- technique flags (the paper's contributions as framework features) ---
    use_pallas: bool = False          # Pallas kernels (TPU target; tests use interpret)
    fused_block: bool = True          # PipeCNN-style stage fusion inside blocks
    attention_impl: str = "chunked"   # "chunked" (online-softmax) | "naive"
    attn_chunk: int = 1024            # KV chunk for chunked attention
    # scan_layers=False unrolls every structural loop (layers, attention/SSM
    # chunks) so XLA cost_analysis counts each iteration — used by the
    # roofline dry-run (scan bodies are otherwise counted once).
    scan_layers: bool = True

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown family {self.family!r}")
        if self.d_head == 0 and self.n_heads:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    # -- derived quantities -------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return max(1, self.ssm_d_inner // self.ssm_headdim)

    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing => can run the 500k decode shape."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (matches init_params; used for 6ND)."""
        from repro.models.lm import count_params  # local import: avoid cycle
        return count_params(self)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top_k experts)."""
        from repro.models.lm import count_params
        return count_params(self, active_only=True)

    # -- smoke-test reduction -------------------------------------------------
    def smoke(self) -> "ModelConfig":
        """A tiny config of the same family for CPU smoke tests."""
        nh = min(self.n_heads, 4) or 4
        nkv = max(1, min(self.n_kv_heads, 2))
        if self.n_kv_heads == self.n_heads:   # MHA stays MHA
            nkv = nh
        n_layers = 4 if self.attn_every or self.family == "ssm" else 2
        return replace(
            self,
            n_layers=n_layers,
            d_model=64,
            n_heads=nh,
            n_kv_heads=nkv,
            d_head=16,
            d_ff=0 if self.d_ff == 0 else 128,
            vocab=512,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=16,
            ssm_chunk=8,
            attn_every=2 if self.attn_every else 0,
            frontend_len=8 if self.frontend_len else 0,
            attn_chunk=16,
            dtype="float32",
            remat=False,
        )


# ---------------------------------------------------------------------------
# Input-shape specifications (assigned shapes)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                          # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_4k", 4_096, 256, "train"),
    ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    ShapeSpec("decode_32k", 32_768, 128, "decode"),
    ShapeSpec("long_500k", 524_288, 1, "decode"),
)


def get_shape(name: str) -> ShapeSpec:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def applicable_shapes(cfg: ModelConfig) -> Tuple[ShapeSpec, ...]:
    """The shapes this architecture runs (long_500k only for sub-quadratic)."""
    out = []
    for s in SHAPES:
        if s.name == "long_500k" and not cfg.supports_long_context():
            continue  # full-attention archs skip 500k decode (see DESIGN.md)
        out.append(s)
    return tuple(out)


# ---------------------------------------------------------------------------
# CNN configuration (the paper's own models)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvLayer:
    kind: str                         # "conv" | "pool" | "lrn" | "fc"
    out_ch: int = 0
    kernel: int = 0
    stride: int = 1
    pad: int = 0
    groups: int = 1                   # AlexNet conv2/4/5 use groups=2
    pool: str = "max"                 # for kind == "pool": "max" | "avg"
    relu: bool = True
    # PipeCNN fusion: pooling fused into the preceding conv's pipeline
    fuse_pool: Optional["ConvLayer"] = None


def fuse_groups(layers: Sequence["ConvLayer"]) -> List[Tuple[int, ...]]:
    """Group layer indices into PipeCNN pipeline stages.

    conv immediately followed by pool -> fused (conv+pool) kernel
    launch; lrn stays standalone (off-pipeline, as in the paper); fc
    standalone. THE one grouping implementation: ``models.cnn.fuse_plan``
    executes it and ``CNNConfig.__post_init__`` validates against it, so
    the two can never disagree.
    """
    plan: List[Tuple[int, ...]] = []
    i = 0
    while i < len(layers):
        if (layers[i].kind == "conv" and i + 1 < len(layers)
                and layers[i + 1].kind == "pool"):
            plan.append((i, i + 1))
            i += 2
        else:
            plan.append((i,))
            i += 1
    return plan


@dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: int
    input_ch: int
    n_classes: int
    layers: Tuple[ConvLayer, ...]
    # PipeCNN throughput parameters (VEC_SIZE x CU_NUM design space)
    vec_size: int = 8
    cu_num: int = 16
    use_lrn: bool = False
    dtype: str = "float32"            # the paper implements full fp32
    # fixed-point serving (the paper's precision/resource trade, PR 3):
    # "none" = fp32; "int8" = calibrated symmetric int8 pipeline (int8
    # conv/FC kernels with int32 accumulation + requantize epilogues).
    # "int8" declares the model must be served from QuantizedCNNParams —
    # compile_cnn calibrates raw fp32 params under it.
    quant: str = "none"
    # calibration images the serving path synthesises when quant="int8"
    # and no QuantizedCNNParams / calibration batch is handed in; 0 means
    # "no calibration source" and is rejected together with quant="int8"
    calib: int = 8
    # --- spatial tiling / DSE (the Fig. 7 sweep, per layer) ---
    oh_blk: int = 0                   # line-buffer depth in conv rows (0=full)
    autotune: bool = True             # per-layer (b,c,m,oh)_blk DSE
    vmem_budget: int = 16 * 2 ** 20   # per-core VMEM the tuner must fit
    # --- batched serving (the paper's batch-64 FC mode, PR 2) ---
    b_blk: int = 1                    # images per conv grid step when
    #                                   autotune is off (manual fallback)
    serve_batch: int = 64             # micro-batch the serving launcher
    #                                   pads requests to (paper: batch 64)
    # --- distributed serving (the fleet engine, PR 4) ---
    replicas: int = 1                 # data-parallel replicas (mesh "data")
    pp_stages: int = 1                # pipeline stages (mesh "pipe")
    serve_microbatches: int = 0       # GPipe microbatches per round (0=auto)
    max_queue: int = 0                # admission bound per replica queue
    #                                   (0 = unbounded, no rejections)

    def __post_init__(self):
        """Cross-validate the knob combinations at CONSTRUCTION time.

        These used to fail deep inside pallas tracing (a shape error five
        frames into an index map) or silently misconfigure a run; the
        config is the first place every entry point passes through, so it
        is where contradictions are cheapest to reject.
        """
        if self.quant not in ("none", "int8"):
            raise SpecError(
                "CNNConfig.quant",
                f"CNNConfig.quant={self.quant!r}: expected 'none' or 'int8'")
        if self.quant == "int8" and self.calib <= 0:
            raise SpecError(
                "CNNConfig.calib",
                "CNNConfig.quant='int8' needs a calibration source: set "
                "calib > 0 (the synthetic calibration-batch size; unused "
                "— but still required — when pre-calibrated "
                "QuantizedCNNParams are handed to compile/forward)")
        if self.replicas < 1 or self.pp_stages < 1:
            raise SpecError(
                "CNNConfig.replicas",
                f"CNNConfig.replicas={self.replicas} / "
                f"pp_stages={self.pp_stages}: both must be >= 1")
        n_groups = self.n_fuse_groups
        if self.layers and self.pp_stages > n_groups:
            raise SpecError(
                "CNNConfig.pp_stages",
                f"CNNConfig.pp_stages={self.pp_stages} exceeds the "
                f"{n_groups} indivisible fusion groups of {self.name!r}; "
                f"a pipeline stage cannot be finer than one fused "
                f"conv(+pool) launch — lower pp_stages to <= {n_groups}")
        if self.b_blk > 1 and self.serve_batch % self.b_blk:
            raise SpecError(
                "CNNConfig.serve_batch",
                f"CNNConfig.serve_batch={self.serve_batch} is not a "
                f"multiple of b_blk={self.b_blk}: the serving queue pads "
                f"requests to serve_batch, so the conv grid's image block "
                f"must divide it (pick b_blk in "
                f"{[d for d in range(1, self.serve_batch + 1) if self.serve_batch % d == 0]})")

    @property
    def n_fuse_groups(self) -> int:
        """Count of indivisible pipeline fusion groups (the grouping
        ``models.cnn.fuse_plan`` executes — one shared implementation,
        :func:`fuse_groups`)."""
        return len(fuse_groups(self.layers))

    def smoke(self) -> "CNNConfig":
        """Shrink channel counts for CPU tests (same topology)."""
        def shrink(l: ConvLayer) -> ConvLayer:
            return replace(l, out_ch=max(8, l.out_ch // 16) if l.out_ch else 0)
        return replace(self, layers=tuple(shrink(l) for l in self.layers),
                       n_classes=16, input_hw=min(self.input_hw, 67))


def flops_per_image(cfg: CNNConfig) -> int:
    """Multiply-accumulate op count (2 ops per MAC), as GOPS in the paper."""
    h = w = cfg.input_hw
    c = cfg.input_ch
    total = 0
    for l in cfg.layers:
        if l.kind == "conv":
            h = (h + 2 * l.pad - l.kernel) // l.stride + 1
            w = (w + 2 * l.pad - l.kernel) // l.stride + 1
            total += 2 * h * w * l.out_ch * l.kernel * l.kernel \
                * (c // l.groups)
            c = l.out_ch
        elif l.kind == "pool":
            h = (h - l.kernel) // l.stride + 1
            w = (w - l.kernel) // l.stride + 1
        elif l.kind == "fc":
            total += 2 * c * h * w * l.out_ch
            h = w = 1
            c = l.out_ch
    return total
