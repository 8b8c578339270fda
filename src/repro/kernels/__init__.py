"""Pallas TPU kernels for the secure-aggregation hot path.

mask_gen    — fused pairwise-mask generation + application (the O(n^2) MPC cost)
quantize    — fixed-point quantize / dequantize for modular masking
secure_sum  — stage-1 wrapping uint32 reduction over the client axis
dp_noise    — fused DP clip-scale + in-kernel Gaussian noise

ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles.
Kernels run in interpret mode on the CPU backend (the test suite) and
compile for the TPU everywhere else (``common.interpret_mode``).
"""
