"""Operations and bytes the ResNet-50 featurize forward needs, from the
architecture's shapes alone (the configuration file's table).

FLOPs = 2 x multiply-accumulates of every convolution up to `avgpool`; the
classifier head is not part of a feature row. With the stride on the 3x3
convolution (v1.5, as the configuration states) this counts 4.09 GMAC an
image at 224x224 with every window tap counted, and 3.95 with the taps on the
zero padding left out, which is what is counted here; the paper's v1 placement
counts 3.8. XLA's own count is 7.99 GFLOP an image for the program's
forward (ISSUE 24) and 7.94 for the plain reference, both compiled for a v5e;
that includes the batch-norm, pooling and element-wise work, which this leaves
out. `selfcheck/test_work.py` holds the two within 3%.
"""

from __future__ import annotations

from typing import Dict


def _out(size: int, stride: int) -> int:
    return -(-size // stride)          # SAME padding


def _taps(size: int, k: int, stride: int) -> int:
    """Window positions along one axis that fall on the input, summed over
    the outputs: a tap on the zero padding is no multiply-accumulate."""
    out = _out(size, stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - lo < size)


def macs_per_image(config) -> int:
    width, exp = int(config["width"]), int(config["bottleneck_expansion"])
    image = int(config["image_size"])
    size = _out(image, 2)
    macs = _taps(image, 7, 2) ** 2 * int(config["channels"]) * width  # stem
    size = _out(size, 2)                                              # max pool
    cin = width
    for i, n in enumerate(config["stage_blocks"]):
        mid = width * 2 ** i
        for j in range(int(n)):
            stride = 2 if (i > 0 and j == 0) else 1
            out = _out(size, stride)
            macs += size * size * cin * mid                # 1x1, input resolution
            macs += _taps(size, 3, stride) ** 2 * mid * mid  # 3x3, carries the stride
            macs += out * out * mid * mid * exp            # 1x1
            if stride != 1 or cin != mid * exp:
                macs += out * out * cin * mid * exp        # projection shortcut
            cin, size = mid * exp, out
    return macs


def flops_per_image(config) -> float:
    return 2.0 * macs_per_image(config)


def conv_parameters(config) -> int:
    """Parameters the feature forward reads: everything but the classifier."""
    feat = int(config["feature_width"])
    classes = int(config["num_classes"])
    return int(config["parameters"]) - (feat * classes + classes)


def bytes_per_batch(config, batch: int) -> Dict[str, float]:
    """Bytes one batch has to move, as the algorithm needs them: the weights
    once (float32, as the program holds them), the uint8 input batch, the
    float32 feature rows. Not XLA's `bytes accessed`, which counts every
    intermediate."""
    size, ch = int(config["image_size"]), int(config["channels"])
    return {"weights": 4.0 * conv_parameters(config),
            "input": float(batch * size * size * ch),
            "output": 4.0 * batch * int(config["feature_width"])}
