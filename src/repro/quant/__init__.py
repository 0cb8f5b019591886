"""Fixed-point (int8) inference subsystem — the paper's precision trade.

PipeCNN runs its deeply pipelined kernels in fixed-point, buying a 34%
DSP-block reduction at 33.9 GOPS; on the TPU analogue int8 quarters HBM
traffic on the bandwidth-bound conv layers and doubles MXU op throughput.
This package holds the one quantization codepath repo-wide:

  * :mod:`repro.quant.core` — symmetric quantize / dequantize / fake-quant
    primitives (also backing ``optim.compress``'s gradient compression);
  * :mod:`repro.quant.observers` — calibration range observers;
  * :mod:`repro.quant.calibrate` — activation calibration + per-channel
    weight quantization -> :class:`QuantizedCNNParams`;
  * :mod:`repro.quant.ref` — exact-int32 and fake-quant reference paths
    (the ground truth the int8 Pallas kernels are tested against).

The execution side lives with the kernels: ``kernels.conv_pipe`` /
``kernels.matmul_pipe`` take int8 operands with a ``scale`` vector and a
static ``out_scale`` and fuse the requantize -> bias -> ReLU -> pool
epilogue; ``models.cnn.run_group`` runs a group in int8 when handed a
:class:`QuantizedCNNParams`.
"""
from repro.quant.calibrate import (QuantizedCNNParams, QuantLayer,
                                   calibrate_cnn, group_forward_ref)
from repro.quant.core import (QMAX, abs_max_scale, dequantize,
                              dequantize_blocks, fake_quant, quantize,
                              quantize_blocks, quantize_channelwise)
from repro.quant.observers import (AbsMaxObserver,
                                   MovingAverageAbsMaxObserver,
                                   make_observer)

__all__ = [
    "QMAX", "AbsMaxObserver", "MovingAverageAbsMaxObserver", "QuantLayer",
    "QuantizedCNNParams", "abs_max_scale", "calibrate_cnn", "dequantize",
    "dequantize_blocks", "fake_quant", "group_forward_ref", "make_observer",
    "quantize", "quantize_blocks", "quantize_channelwise",
]
