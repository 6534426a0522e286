"""Plain reference of the ``streamsplit-audio`` configuration.

The paper's encoder written out in ``jax.numpy``: a kernel-7 stem
convolution, eight residual blocks (two kernel-3 convolutions with
GroupNorm, a 1x1 projection where the width or stride changes), mean
pooling and a linear head to an l2-normalized d=128 embedding.  A frame
served at split point k runs blocks [0, k), crosses the split link as
per-sample asymmetric int8 (min/max scale, float zero point) and runs
blocks [k, L) and the head; k = 0 sends the mel, k = L the embedding.

It imports nothing of the program.  The benchmark makes the weights
here, from the seed, and hands the same tree to the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def init_params(enc, key):
    """Seeded weights in the tree the program serves: truncated-normal
    convolutions scaled 1/sqrt(fan-in), and GroupNorm affines spread
    around (1, 0) so that the comparison covers them."""
    widths = enc["widths"]
    keys = iter(jax.random.split(key, 4 + 8 * len(widths)))

    def conv(k, cin, cout):
        return (jax.random.truncated_normal(next(keys), -2.0, 2.0,
                                            (k, cin, cout), jnp.float32)
                / math.sqrt(k * cin))

    def norm(w):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (w,)),
                "bias": 0.1 * jax.random.normal(next(keys), (w,))}

    params = {"stem": {"w": conv(7, enc["n_mels"], widths[0])}}
    blocks, cin = [], widths[0]
    for w, s in zip(widths, enc["strides"]):
        blk = {"conv1": {"w": conv(enc["kernel"], cin, w)}, "gn1": norm(w),
               "conv2": {"w": conv(enc["kernel"], w, w)}, "gn2": norm(w)}
        if s != 1 or cin != w:
            blk["proj"] = {"w": conv(1, cin, w)}
        blocks.append(blk)
        cin = w
    params["blocks"] = blocks
    params["head"] = {"w": conv(1, cin, enc["d_embed"])[0]}
    return params


def _conv(x, w, stride, precision):
    return lax.conv_general_dilated(
        x, w, (stride,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"),
        precision=precision)


def _norm(p, x, groups, eps=1e-5):
    b, t, c = x.shape
    g = x.reshape(b, t, groups, c // groups)
    mu = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mu) ** 2).mean(axis=(1, 3), keepdims=True)
    g = (g - mu) / jnp.sqrt(var + eps)
    return g.reshape(b, t, c) * p["scale"] + p["bias"]


def _blocks(enc, params, x, start, end, precision):
    for i in range(start, end):
        blk, s = params["blocks"][i], enc["strides"][i]
        h = _conv(x, blk["conv1"]["w"], s, precision)
        h = jax.nn.relu(_norm(blk["gn1"], h, enc["groups"]))
        h = _conv(h, blk["conv2"]["w"], 1, precision)
        h = _norm(blk["gn2"], h, enc["groups"])
        if "proj" in blk:
            x = _conv(x, blk["proj"]["w"], s, precision)
        x = jax.nn.relu(x + h)
    return x


def _head(params, x):
    pooled = x.mean(axis=1)
    z = jnp.dot(pooled, params["head"]["w"], precision=HIGHEST)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-6)


def wire(x):
    """Per-sample asymmetric int8 quantize then dequantize, in float32."""
    flat = x.reshape(x.shape[0], -1)
    lo = flat.min(axis=1, keepdims=True)
    hi = flat.max(axis=1, keepdims=True)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-12)
    zero = -128.0 - lo / scale
    q = jnp.clip(jnp.round(flat / scale + zero), -128, 127)
    return ((q - zero) * scale).reshape(x.shape)


def embed(enc, params, mel, k, *, precision=HIGHEST, dtype=jnp.float32):
    """Embeddings of a batch of mels served at split point ``k``.

    ``precision`` is that of the convolutions' products; the head's
    projection is at ``HIGHEST``.  ``dtype`` is the type every activation,
    normalization and weight is stored and computed in (the wire
    round-trip aside, which works in float32)."""
    L = len(enc["widths"])
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    run = partial(_blocks, enc, params, precision=precision)
    conv = partial(_conv, precision=precision)
    x = mel.astype(dtype)
    if k > 0:
        x = jax.nn.relu(conv(x, params["stem"]["w"], 1))
        x = run(x, 0, k)
    if k < L:
        x = wire(x.astype(jnp.float32)).astype(dtype)
        if k == 0:
            x = jax.nn.relu(conv(x, params["stem"]["w"], 1))
        x = run(x, k, L)
    return _head(params, x).astype(jnp.float32)


def reference(enc, params, mel, k):
    """The configuration at the precision it states: float32, the
    convolutions' products at the TPU's default precision (one bfloat16
    pass), the head's projection exact."""
    return embed(enc, params, mel, k, precision=None)


def control(enc, params, mel, k):
    """The control: the same computed one precision below the stated
    float32, in bfloat16 throughout (weights, activations, normalization,
    residuals and products)."""
    return embed(enc, params, mel, k, precision=None, dtype=jnp.bfloat16)
