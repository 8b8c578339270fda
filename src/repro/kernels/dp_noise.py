"""Pallas kernel: fused DP clip-scale + Gaussian noise (paper §4.2).

Given the precomputed clip factor (min(1, C/||u||), a scalar — the global
norm is a cheap separate reduction), this fuses the rescale and the Gaussian
noise draw into one pass over the update vector. Noise is generated in-kernel
from the counter KDF via Box–Muller, so (as with masks) random words never
touch HBM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (LANES, ROW_BLOCK, global_index,
                                  interpret_mode, kdf_u32, u32_to_f32)

TWO_PI = 6.2831853071795864


def _box_muller(k0, k1, ctr):
    """Two KDF words -> one standard normal (f32). Bit-matched in ref.py."""
    b1 = kdf_u32(k0, k1, ctr * jnp.uint32(2))
    b2 = kdf_u32(k0, k1, ctr * jnp.uint32(2) + jnp.uint32(1))
    # u1 in (0, 1]: (b1 + 1) / 2^32 ; u2 in [0, 1)
    u1 = (u32_to_f32(b1) + 1.0) * (1.0 / 4294967296.0)
    u2 = u32_to_f32(b2) * (1.0 / 4294967296.0)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(TWO_PI * u2)


def _dp_noise_kernel(scale_ref, seed_ref, x_ref, out_ref, *, sigma):
    pid = pl.program_id(0)
    ctr = global_index(pid)
    z = _box_muller(seed_ref[0, 0], seed_ref[0, 1], ctr)
    out_ref[...] = x_ref[...] * scale_ref[0, 0] + jnp.float32(sigma) * z


def dp_clip_noise_tiled(x_tiled, clip_factor, sigma, seed, *, interpret=None):
    """x_tiled (rows,128) f32; clip_factor scalar; seed (2,) uint32."""
    rows = x_tiled.shape[0]
    assert rows % ROW_BLOCK == 0 and x_tiled.shape[1] == LANES
    interpret = interpret_mode() if interpret is None else interpret
    scale = jnp.asarray(clip_factor, jnp.float32).reshape(1, 1)
    seed = jnp.asarray(seed, jnp.uint32).reshape(1, 2)
    return pl.pallas_call(
        partial(_dp_noise_kernel, sigma=float(sigma)),
        grid=(rows // ROW_BLOCK,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            pl.BlockSpec((ROW_BLOCK, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x_tiled.shape, jnp.float32),
        interpret=interpret,
    )(scale, seed, x_tiled)
