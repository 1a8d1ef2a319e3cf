"""Operations and bytes, counted from shapes: the yardstick of ``mfu``
and of the kernels' roofline shares.

``hbm_bytes`` counts what a kernel call must move at the least; each
kernel's roofline reader (``bench/metrics/<kernel>_roofline.py``) names
its kernels and counts their arithmetic.  ``*_params`` count a
configuration's parameters from its file, and are tied to the program's
own counts by a test.
"""
from __future__ import annotations

import math

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1}


def elems(dims) -> int:
    return int(math.prod(dims)) if dims else 1


def hbm_bytes(results, operands) -> int:
    """The least HBM bytes a kernel call moves: every result written and
    every operand read once, from the ``(dtype, dims, in_hbm)`` of each;
    an array XLA placed in on-chip memory moves none."""
    return sum(DTYPE_BYTES[dt] * elems(d)
               for dt, d, hbm in list(results) + list(operands) if hbm)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def mlp_params(cfg: dict) -> int:
    m = cfg["spec"]["model"]
    dims = [math.prod(m["input_shape"])] + list(m["hidden"]) + \
        [m["num_classes"]]
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def padded_vocab(cfg: dict) -> int:
    """Embedding rows: the vocabulary padded to a multiple of
    ``pad_vocab_size_multiple``, as the source pads it."""
    mult = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // mult) * mult


def mamba2_params(cfg: dict) -> int:
    """Embedding (tied), final norm, and per layer: norm, in_proj (z, x,
    B, C, dt), depthwise conv (weight and bias over x, B, C), A, dt bias,
    D, gated norm, out_proj."""
    d, n_layer = cfg["d_model"], cfg["n_layer"]
    d_inner = cfg["expand"] * d
    heads = d_inner // cfg["headdim"]
    bc = 2 * cfg["ngroups"] * cfg["d_state"]
    conv_ch = d_inner + bc
    layer = (d                                   # pre-norm
             + d * (2 * d_inner + bc + heads)    # in_proj
             + cfg["d_conv"] * conv_ch + conv_ch  # conv weight, bias
             + 3 * heads                         # A_log, dt_bias, D
             + d_inner                           # gated norm
             + d_inner * d)                      # out_proj
    emb = padded_vocab(cfg) * d * (1 if cfg["tie_embeddings"] else 2)
    return emb + d + n_layer * layer


def train_flops(params: int, examples: float) -> float:
    """Forward and backward of a dense model: 6 operations per parameter
    per example (token) processed; recomputation is not counted."""
    return 6.0 * params * examples
