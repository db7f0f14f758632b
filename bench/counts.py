"""Operations and bytes of one ResNet-50 forward pass, from its shapes.

The benchmark's own arithmetic, independent of the program: the layer
table of ResNet-50 v1.5 (He et al. 2016, stride on the 3x3 convolution of
each stage's first block), NHWC inputs, "SAME" padding. A multiply-add is
two operations. Batch-norm scale and bias, ReLU, pooling and the residual
additions are left out of the operation count: they are under 1% of it
and run on the vector units, not against the bf16 matrix peak.
"""
from __future__ import annotations

import math

BUCKETS = (1, 2, 4, 8, 16)     # the batch sizes the daemon compiles
STAGES = (3, 4, 6, 3)          # bottleneck blocks per stage
WIDTHS = (64, 128, 256, 512)   # bottleneck width per stage (output is 4x)
EXPANSION = 4


def widths(scale: int = 1):
    """Stage widths; `scale` > 1 is the 1/scale-width model of CPU tests."""
    return tuple(max(8, w // scale) for w in WIDTHS)


def conv_layers(img: int = 224, scale: int = 1):
    """(name, out_h, out_w, k, cin, cout) for every convolution."""
    ws = widths(scale)
    h = math.ceil(img / 2)                     # 7x7 stem, stride 2
    yield ("stem", h, h, 7, 3, ws[0])
    h = math.ceil(h / 2)                       # 3x3 max pool, stride 2
    cin = ws[0]
    for si, (n, w) in enumerate(zip(STAGES, ws)):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            h_out = math.ceil(h / stride)
            cout = w * EXPANSION
            tag = f"stage{si}.{bi}"
            yield (f"{tag}.conv1", h, h, 1, cin, w)
            yield (f"{tag}.conv2", h_out, h_out, 3, w, w)
            yield (f"{tag}.conv3", h_out, h_out, 1, w, cout)
            if stride != 1 or cin != cout:
                yield (f"{tag}.proj", h_out, h_out, 1, cin, cout)
            cin, h = cout, h_out


def flop_per_image(img: int = 224, scale: int = 1,
                   num_classes: int = 1000) -> int:
    """Operations of one image's forward pass: convolutions and the head."""
    macs = sum(oh * ow * k * k * cin * cout
               for _, oh, ow, k, cin, cout in conv_layers(img, scale))
    head_in = widths(scale)[-1] * EXPANSION
    return 2 * (macs + head_in * num_classes)


def param_count(scale: int = 1, num_classes: int = 1000) -> int:
    """Weights, with each batch norm's scale and bias."""
    n = 0
    for _, _, _, k, cin, cout in conv_layers(224, scale):
        n += k * k * cin * cout + 2 * cout
    return n + widths(scale)[-1] * EXPANSION * num_classes


def bytes_per_exec(batch: int, img: int = 224, scale: int = 1,
                   num_classes: int = 1000, weight_bytes: int = 2,
                   input_bytes: int = 4, logit_bytes: int = 2) -> int:
    """The least HBM traffic of one batch: every weight read once (bf16),
    the float32 input read and the bf16 logits written. Activations that
    spill to HBM would add to it, so this bounds the time from below."""
    return (param_count(scale, num_classes) * weight_bytes
            + batch * img * img * 3 * input_bytes
            + batch * num_classes * logit_bytes)


def roofline_s(batch: int, peak: dict, img: int = 224, scale: int = 1,
               num_classes: int = 1000) -> float:
    """Least time one forward of `batch` images can take on a chip with
    `peak` (see peaks.py): the larger of its compute and its memory time."""
    return max(batch * flop_per_image(img, scale, num_classes)
               / peak["bf16_flop_s"],
               bytes_per_exec(batch, img, scale, num_classes)
               / peak["hbm_bytes_s"])
