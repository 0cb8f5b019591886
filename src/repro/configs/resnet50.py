"""ResNet-50 v1.5 [arXiv:1512.03385, Table 1, the 50-layer column].

224x224x3 input; a 7x7/2 stem conv and a padded 3x3/2 max pool; stages
of 3, 4, 6 and 3 bottleneck blocks (1x1, 3x3, 1x1 at widths 64, 128,
256, 512, expansion 4); global average pool; FC 2048 -> 1000. v1.5 puts
each block's stride on its 3x3 conv, as torchvision's ``resnet50`` and
the MLPerf Inference ResNet-50 benchmark do. Batch-norm is folded into
each conv's weight and bias, so there are no BN layers: 53 convs, 16
adds, 25,530,472 weights, 8,178,368,512 FLOP an image.

A block's last 1x1 conv has no ReLU: the add that ends the block sums
it with the block's input (a stage's first block: with a 1x1 projection
of it at the block's stride, also without ReLU) and applies the ReLU.
"""
from repro.core.config import CNNConfig, ConvLayer


def _conv(out_ch, k, stride=1, relu=True, inputs=()):
    return ConvLayer("conv", out_ch=out_ch, kernel=k, stride=stride,
                     pad=k // 2, relu=relu, inputs=inputs)


def _layers():
    layers = [_conv(64, 7, 2),
              ConvLayer("pool", kernel=3, stride=2, pad=1)]
    for n, width, stride in ((3, 64, 1), (4, 128, 2), (6, 256, 2),
                             (3, 512, 2)):
        for b in range(n):
            x = len(layers) - 1                     # the block's input
            s = stride if b == 0 else 1
            layers += [_conv(width, 1), _conv(width, 3, s),
                       _conv(4 * width, 1, relu=False)]
            main, short = len(layers) - 1, x
            if b == 0:
                layers.append(_conv(4 * width, 1, s, relu=False,
                                    inputs=(x,)))
                short = len(layers) - 1
            layers.append(ConvLayer("add", inputs=(main, short)))
    return (*layers, ConvLayer("gap"),
            ConvLayer("fc", out_ch=1000, relu=False))


CONFIG = CNNConfig(
    name="resnet50",
    input_hw=224,
    input_ch=3,
    n_classes=1000,
    use_lrn=False,
    layers=_layers(),
)
