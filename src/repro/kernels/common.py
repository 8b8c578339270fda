"""Shared Pallas kernel helpers: tiling geometry + in-kernel KDF rounds.

TPU geometry: lanes are 128-wide, the VPU operates on (8, 128) uint32 tiles,
so every kernel here works on payloads reshaped to (rows, 128) with row
blocks that are multiples of 8. ``pad_to_tiles`` / ``unpad`` handle arbitrary
flat payload sizes at the ops.py boundary.

The in-kernel ``kdf_u32`` is bit-identical to ``repro.core.kdf.kdf_u32``
(pure uint32 ARX ops — the same jnp code runs inside the kernel body).

Mosaic (the TPU kernel compiler) refuses direct uint32 <-> float32 casts;
``u32_to_f32`` / ``f32_to_u32`` route them through int32, bit-identical to
the direct casts the jnp references use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.kdf import kdf_u32  # bit-identical inside kernel bodies

LANES = 128
ROW_BLOCK = 256        # (256, 128) uint32 = 128 KiB per operand block in VMEM


def interpret_mode() -> bool:
    """Interpret kernels on the CPU backend only. Any other backend
    compiles them (and a backend Pallas cannot compile for fails loudly)
    instead of quietly falling back to the interpreter."""
    return jax.default_backend() == "cpu"


def u32_to_f32(x):
    """uint32 -> float32, rounded once to nearest-even like the direct
    cast: both 16-bit halves convert exactly through int32, ``hi * 2^16``
    is exact, and the one addition rounds."""
    hi = (x >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def f32_to_u32(x):
    """Non-negative integral float32 below 2^31 -> uint32 (quantization
    codes for every ``bits <= 31``), via int32."""
    return x.astype(jnp.int32).astype(jnp.uint32)


def pad_to_tiles(flat, block_rows=ROW_BLOCK):
    """flat (N,) -> (rows, 128) with rows % block_rows == 0. Returns
    (tiled, original_n)."""
    n = flat.shape[0]
    per_block = block_rows * LANES
    padded = (n + per_block - 1) // per_block * per_block
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANES), n


def unpad(tiled, n):
    return tiled.reshape(-1)[:n]


def global_index(pid, block_rows=ROW_BLOCK):
    """uint32 flat element indices for grid cell ``pid``: (block_rows, 128)."""
    base = (pid * block_rows * LANES).astype(jnp.uint32)
    row = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANES), 1)
    return base + row * jnp.uint32(LANES) + lane
