"""The benchmark's fixed arithmetic: chip peaks, and the operations and
bytes of the work it counts.

Everything here is computed from the configuration's shapes, never read
from the program, so a later change to the program cannot move the
yardstick it is measured against.
"""
from __future__ import annotations

import math

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  A kind
# that is not here is an error: a share of an unknown peak is no number.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: "
                  "197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at "
                  "819 GB/s)",
    },
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


def conv_out_len(t, stride):
    """Output length of a 'SAME' strided 1-D convolution."""
    return math.ceil(t / stride)


def boundary_elements(enc, k):
    """Elements of one sample's activation at split point k (0 < k <= L
    is the output of block k-1; k = 0 is the mel input)."""
    t = enc["frames"]
    if k == 0:
        return t * enc["n_mels"]
    for s in enc["strides"][:k]:
        t = conv_out_len(t, s)
    return t * enc["widths"][k - 1]


def encoder_flops(enc):
    """Forward FLOPs of one frame through the whole encoder: the stem
    convolution, the 8 residual blocks (two convolutions each, plus the
    1x1 projection where the width or stride changes) and the
    projection head.  Multiply-adds count 2; normalization, activations
    and pooling are not counted."""
    t = enc["frames"]
    kk = enc["kernel"]
    c0 = enc["widths"][0]
    flops = 2 * 7 * enc["n_mels"] * c0 * t            # stem, kernel 7
    cin = c0
    for w, s in zip(enc["widths"], enc["strides"]):
        t = conv_out_len(t, s)
        flops += 2 * kk * cin * w * t + 2 * kk * w * w * t
        if s != 1 or cin != w:
            flops += 2 * cin * w * t
        cin = w
    flops += 2 * cin * enc["d_embed"]                 # head
    return flops


def wire_roundtrip_bytes(enc, k, rows):
    """HBM bytes one call of the wire kernel must move for ``rows``
    samples at split point k: each float32 element read once and its
    round-tripped float32 value written once."""
    return 8 * rows * boundary_elements(enc, k)


def roofline_time_s(flops, nbytes, pk):
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
