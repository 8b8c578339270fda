"""Pallas kernels: affine fixed-point quantize / dequantize (paper §4.1).

Elementwise, VPU-bound; tiled (ROW_BLOCK, 128). Matches
``repro.core.quantize`` bit-exactly (same f32 rounding sequence).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (LANES, ROW_BLOCK, f32_to_u32,
                                  interpret_mode, u32_to_f32)


def _quantize_kernel(x_ref, out_ref, *, clip, bits):
    lv = jnp.float32((1 << bits) - 1)
    xf = jnp.clip(x_ref[...].astype(jnp.float32), -clip, clip)
    q = jnp.round((xf + clip) / (2.0 * clip) * lv)
    out_ref[...] = f32_to_u32(q)


def _dequantize_kernel(q_ref, out_ref, *, clip, bits, n):
    # same op sequence as core.quantize.dequantize_sum (bit-exact)
    lv = jnp.float32((1 << bits) - 1)
    mean_code = u32_to_f32(q_ref[...]) / jnp.float32(n)
    out_ref[...] = (mean_code / lv) * (2.0 * clip) - clip


def _elementwise_call(kernel, x, out_dtype, interpret):
    rows = x.shape[0]
    return pl.pallas_call(
        kernel,
        grid=(rows // ROW_BLOCK,),
        in_specs=[pl.BlockSpec((ROW_BLOCK, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=interpret,
    )(x)


def quantize_tiled(x_tiled, clip, bits, *, interpret=None):
    """x_tiled: (rows, 128) f32 -> (rows, 128) uint32 codes."""
    interpret = interpret_mode() if interpret is None else interpret
    return _elementwise_call(
        partial(_quantize_kernel, clip=float(clip), bits=int(bits)),
        x_tiled, jnp.uint32, interpret)


def dequantize_sum_tiled(q_tiled, n, clip, bits, *, interpret=None):
    """(rows,128) uint32 aggregate-sum codes -> f32 cohort-mean values."""
    interpret = interpret_mode() if interpret is None else interpret
    return _elementwise_call(
        partial(_dequantize_kernel, clip=float(clip), bits=int(bits),
                n=int(n)),
        q_tiled, jnp.float32, interpret)
