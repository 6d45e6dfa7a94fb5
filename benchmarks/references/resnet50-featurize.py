"""Plain reference of the ResNet-50 featurize configuration.

Written from the paper's Table 1 (He et al. 2015, "50-layer" column) in
straightforward `jax.numpy` / `lax.conv_general_dilated`, float32 at
`highest` precision, no kernels, no batching machinery. Imports nothing of
the program under test and takes nothing it made: the weights come from the
seed (`make_weights`), the images from the harness.

Departures from the paper, as the configuration file states them: stride on
the 3x3 convolution of a down-sampling block (v1.5), XLA "SAME" padding,
inference-mode batch norm with stored statistics.

`forward(..., quant="fp8")` is the control: the same computation with the
operands of every convolution rounded to float8 (e4m3, one scale per
tensor), the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

Spec = Tuple[str, Tuple[int, ...], str]        # (path, shape, kind)


def _conv_bn(prefix: str, k: int, cin: int, cout: int, last: bool) -> List[Spec]:
    head, leaf = prefix.rsplit("/", 1)
    bn = f"{head}/{leaf.replace('conv', 'bn')}"
    return [(prefix + "/kernel", (k, k, cin, cout), "conv"),
            (bn + "/scale", (cout,), "bn_scale_last" if last else "bn_scale"),
            (bn + "/bias", (cout,), "bn_bias"),
            (bn + "/mean", (cout,), "bn_mean"),
            (bn + "/var", (cout,), "bn_var")]


def blocks(config) -> List[Tuple[str, int, int, int, bool]]:
    """(path, in channels, mid channels, stride, has projection) of every
    bottleneck block, in order, from the configuration's table."""
    width, exp = int(config["width"]), int(config["bottleneck_expansion"])
    out, cin = [], width
    for i, n in enumerate(config["stage_blocks"]):
        mid = width * 2 ** i
        for j in range(int(n)):
            stride = 2 if (i > 0 and j == 0) else 1
            out.append((f"layer{i + 1}/{j}", cin, mid, stride,
                        stride != 1 or cin != mid * exp))
            cin = mid * exp
    return out


def weight_specs(config) -> List[Spec]:
    width, exp = int(config["width"]), int(config["bottleneck_expansion"])
    specs = _conv_bn("stem/conv", 7, int(config["channels"]), width, False)
    for path, cin, mid, _, proj in blocks(config):
        specs += _conv_bn(f"{path}/body/conv1", 1, cin, mid, False)
        specs += _conv_bn(f"{path}/body/conv2", 3, mid, mid, False)
        specs += _conv_bn(f"{path}/body/conv3", 1, mid, mid * exp, True)
        if proj:
            specs += _conv_bn(f"{path}/shortcut/conv", 1, cin, mid * exp, False)
    feat = width * 2 ** (len(config["stage_blocks"]) - 1) * exp
    specs += [("fc/kernel", (feat, int(config["num_classes"])), "dense"),
              ("fc/bias", (int(config["num_classes"]),), "bn_bias")]
    return specs


def seed_key(seed: int):
    """A PRNG key from any whole number, also one over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def make_weights(config, seed: int) -> Dict[str, "jax.Array"]:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    specs = weight_specs(config)

    def gen(key):
        out = {}
        for i, (path, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "conv":
                fan_in = shape[0] * shape[1] * shape[2]
                w = jax.random.normal(k, shape, jnp.float32) * np.float32(
                    np.sqrt(2.0 / fan_in))
            elif kind == "dense":
                w = jax.random.normal(k, shape, jnp.float32) * np.float32(
                    1.0 / np.sqrt(shape[0]))
            elif kind == "bn_scale":
                w = jax.random.uniform(k, shape, jnp.float32, 0.6, 1.0)
            elif kind == "bn_scale_last":
                w = jax.random.uniform(k, shape, jnp.float32, 0.1, 0.3)
            elif kind == "bn_var":
                w = jax.random.uniform(k, shape, jnp.float32, 0.8, 1.2)
            else:  # bn_bias, bn_mean
                w = jax.random.normal(k, shape, jnp.float32) * np.float32(0.1)
            out[path] = w
        return out

    return jax.jit(gen)(seed_key(seed))


def resize_bilinear_u8(img: np.ndarray, height: int, width: int) -> np.ndarray:
    """Bilinear resize, half-pixel centres, float64, rounded half-to-even to
    uint8: the published semantics of OpenCV's INTER_LINEAR."""
    h, w, _ = img.shape
    if (h, w) == (height, width):
        return img
    ys = (np.arange(height) + 0.5) * h / height - 0.5
    xs = (np.arange(width) + 0.5) * w / width - 0.5
    y0 = np.floor(ys).astype(np.int64)
    x0 = np.floor(xs).astype(np.int64)
    wy = np.clip(ys - y0, 0.0, 1.0)
    wx = np.clip(xs - x0, 0.0, 1.0)
    wy = np.where((y0 < 0) | (y0 > h - 1), 0.0, wy)[:, None, None]
    wx = np.where((x0 < 0) | (x0 > w - 1), 0.0, wx)[None, :, None]
    y0, x0 = np.clip(y0, 0, h - 1), np.clip(x0, 0, w - 1)
    y1, x1 = np.minimum(y0 + 1, h - 1), np.minimum(x0 + 1, w - 1)
    src = img.astype(np.float64)
    top = src[y0][:, x0] * (1 - wx) + src[y0][:, x1] * wx
    bot = src[y1][:, x0] * (1 - wx) + src[y1][:, x1] * wx
    return np.clip(np.rint(top * (1 - wy) + bot * wy), 0, 255).astype(np.uint8)


def _same_pads(size: int, k: int, stride: int) -> Tuple[int, int]:
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def _quant(x, quant: Optional[str]):
    """Round to the control's precision: float8 e4m3 with one scale a tensor."""
    import jax.numpy as jnp

    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _conv(x, w, stride: int, quant: Optional[str]):
    import jax

    k = w.shape[0]
    pads = [_same_pads(x.shape[1], k, stride), _same_pads(x.shape[2], k, stride)]
    return jax.lax.conv_general_dilated(
        _quant(x, quant), _quant(w, quant), (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, w, prefix: str, eps: float):
    import jax.numpy as jnp

    inv = w[prefix + "/scale"] / jnp.sqrt(w[prefix + "/var"] + eps)
    return (x - w[prefix + "/mean"]) * inv + w[prefix + "/bias"]


def features(config, w, x_u8, quant: Optional[str] = None):
    """[B, 224, 224, 3] uint8 -> [B, 2048] float32 pooled features."""
    import jax
    import jax.numpy as jnp

    eps = float(config["assumed"]["batch_norm_eps"])
    x = x_u8.astype(jnp.float32) * np.float32(1 / 255.)
    x = jnp.maximum(_bn(_conv(x, w["stem/conv/kernel"], 2, quant), w, "stem/bn", eps), 0)
    pads = [(0, 0), _same_pads(x.shape[1], 3, 2), _same_pads(x.shape[2], 3, 2), (0, 0)]
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), pads)
    for path, _, _, stride, proj in blocks(config):
        b = path + "/body"
        y = jnp.maximum(_bn(_conv(x, w[b + "/conv1/kernel"], 1, quant), w, b + "/bn1", eps), 0)
        y = jnp.maximum(_bn(_conv(y, w[b + "/conv2/kernel"], stride, quant), w, b + "/bn2", eps), 0)
        y = _bn(_conv(y, w[b + "/conv3/kernel"], 1, quant), w, b + "/bn3", eps)
        if proj:
            s = path + "/shortcut"
            x = _bn(_conv(x, w[s + "/conv/kernel"], stride, quant), w, s + "/bn", eps)
        x = jnp.maximum(x + y, 0)
    return jnp.mean(x, axis=(1, 2))


def featurize(config, seed: int, images: np.ndarray, quant: Optional[str] = None,
              block: int = 32) -> np.ndarray:
    """Features of raw source images [N, H, W, 3] uint8, block by block."""
    import jax

    size = int(config["image_size"])
    w = make_weights(config, seed)
    fwd = jax.jit(lambda w_, x_: features(config, w_, x_, quant))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        pad = block - len(chunk)
        x = np.stack([resize_bilinear_u8(img, size, size) for img in chunk])
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        out.append(np.asarray(fwd(w, x))[:len(chunk)])
    return np.concatenate(out)


def feature_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap of a row from the reference's, against that row's largest
    reference feature."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    scale = np.maximum(np.abs(ref).max(axis=1), 1e-30)
    return float((np.abs(got - ref).max(axis=1) / scale).max())
