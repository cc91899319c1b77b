"""Plain 3-D ResNet (Hara et al., arXiv:1708.07632) for the correctness
comparison: BasicBlocks of two 3x3x3 convolutions, a 3x7x7 stride-2 stem,
stride 2 at the first block of stages 2-4, a 1x1x1 projection shortcut
where the width changes, GroupNorm with 32 groups (scale only, eps 1e-5)
after every convolution, global average pooling and a linear classifier.
Channel-last clips (B, T, H, W, 3).

Written from the paper and the config, importing nothing of the program.
The parameter tree has the layout the program's ``models/resnet3d`` reads,
so that one set of weights made from the seed feeds both.

``dtype`` and ``precision`` select the arithmetic: float32 at ``highest``
is the reference; bfloat16 is the control of the lower precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STAGE_WIDTHS = (1, 2, 4, 8)
GROUPS = 32
EPS = 1e-5


def init_params(key, blocks, stem_width: int, classes: int,
                dtype=jnp.float32) -> dict:
    """He-style normal weights scaled by 1/sqrt(fan_in); GroupNorm scales
    at 1 and the classifier bias at 0."""
    ks = iter(jax.random.split(key, 256))

    def conv(shape):
        return (jax.random.normal(next(ks), shape)
                / math.sqrt(math.prod(shape[:-1]))).astype(dtype)

    w0 = stem_width
    params = {"stem": {"w": conv((3, 7, 7, 3, w0)),
                       "gn": jnp.ones((w0,), dtype)},
              "stages": []}
    c_in = w0
    for si, nblk in enumerate(blocks):
        c_out = w0 * STAGE_WIDTHS[si]
        stage = []
        for bi in range(nblk):
            cin = c_in if bi == 0 else c_out
            blk = {"w1": conv((3, 3, 3, cin, c_out)),
                   "gn1": jnp.ones((c_out,), dtype),
                   "w2": conv((3, 3, 3, c_out, c_out)),
                   "gn2": jnp.ones((c_out,), dtype)}
            if cin != c_out:
                blk["proj"] = conv((1, 1, 1, cin, c_out))
            stage.append(blk)
        params["stages"].append(stage)
        c_in = c_out
    params["fc"] = {"w": conv((c_in, classes)),
                    "b": jnp.zeros((classes,), dtype)}
    return params


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride,) * 3, "SAME",
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"), precision=precision)


def _gn(x, scale):
    B, T, H, W, C = x.shape
    g = math.gcd(GROUPS, C)
    xg = x.reshape(B, T, H, W, g, C // g)
    mean = xg.mean(axis=(1, 2, 3, 5), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 3, 5), keepdims=True)
    y = (xg - mean) / jnp.sqrt(var + EPS)
    return y.reshape(x.shape) * scale


def forward(params, clips, precision=jax.lax.Precision.HIGHEST):
    """Logits (B, classes). Computes in the dtype of ``params``."""
    dt = params["fc"]["w"].dtype
    x = clips.astype(dt)
    x = jax.nn.relu(_gn(_conv(x, params["stem"]["w"], 2, precision),
                        params["stem"]["gn"]))
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if si > 0 and bi == 0 else 1
            h = jax.nn.relu(_gn(_conv(x, blk["w1"], stride, precision),
                                blk["gn1"]))
            h = _gn(_conv(h, blk["w2"], 1, precision), blk["gn2"])
            sc = (_conv(x, blk["proj"], stride, precision) if "proj" in blk
                  else x[:, ::stride, ::stride, ::stride])
            x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2, 3))
    return jnp.dot(x, params["fc"]["w"], precision=precision) \
        + params["fc"]["b"]


def cross_entropy_rows(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold
