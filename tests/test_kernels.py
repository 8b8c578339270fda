"""Per-kernel validation: sweep shapes/dtypes, assert kernels == ref.py
oracles (bit-exact for integer ops, allclose for f32)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SIZES = [5, 128, 4096, 32768, 50_001]
SEED = jnp.asarray([123, 456], jnp.uint32)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("bits", [8, 18, 24])
def test_quantize_kernel_bit_exact(rng, n, bits):
    x = jnp.asarray(rng.uniform(-2, 2, n).astype(np.float32))
    np.testing.assert_array_equal(ops.quantize(x, 1.0, bits),
                                  ref.quantize(x, 1.0, bits))


@pytest.mark.parametrize("n", SIZES)
def test_dequantize_sum_kernel(rng, n):
    q = jnp.asarray(rng.randint(0, 2**24, n, dtype=np.uint32))
    # f32 ops: XLA constant-folds (x/lv)*2c differently inside vs outside
    # the kernel; integer ops stay bit-exact, floats to ~1e-7
    np.testing.assert_allclose(np.asarray(ops.dequantize_sum(q, 7)),
                               np.asarray(ref.dequantize_sum(q, 7)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1000, 40_000])
@pytest.mark.parametrize("i,g", [(0, 2), (1, 4), (7, 8), (3, 5)])
def test_mask_apply_kernel_bit_exact(rng, n, i, g):
    q = jnp.asarray(rng.randint(0, 2**18, n, dtype=np.uint32))
    np.testing.assert_array_equal(ops.mask_apply(q, i, g, SEED),
                                  ref.mask_apply(q, i, g, SEED))


@pytest.mark.parametrize("n_clients,g", [(4, 2), (8, 4), (5, 5), (3, 1)])
@pytest.mark.parametrize("size", [100, 33_000])
def test_mask_apply_cohort_kernel_bit_exact(rng, n_clients, g, size):
    """Batched whole-cohort kernel == per-client oracle, bit for bit
    (including g=1 degenerate groups and ragged client counts)."""
    from repro.core.secure_agg import group_seed
    qs = jnp.asarray(rng.randint(0, 2**18, (n_clients, size),
                                 dtype=np.uint32))
    idxs = jnp.asarray([i % g for i in range(n_clients)], jnp.uint32)
    vgs = jnp.asarray([i // g for i in range(n_clients)], jnp.uint32)
    gseeds = jnp.stack([group_seed(SEED, int(v)) for v in vgs])
    np.testing.assert_array_equal(
        np.asarray(ops.mask_apply_cohort(qs, idxs, gseeds, g)),
        np.asarray(ref.mask_apply_cohort(qs, idxs, gseeds, g)))


def test_build_pair_seeds_traced_matches_static():
    g = 5
    for i in range(g):
        a = ops.build_pair_seeds(i, g, SEED)
        b = ops.build_pair_seeds_traced(jnp.uint32(i), g, SEED)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("clients", [1, 2, 5, 16])
@pytest.mark.parametrize("n", [100, 33_000])
def test_secure_sum_kernel_bit_exact(rng, clients, n):
    p = jnp.asarray(rng.randint(0, 2**32, (clients, n), dtype=np.uint32))
    np.testing.assert_array_equal(ops.secure_sum(p), ref.secure_sum(p))


@pytest.mark.parametrize("n", [256, 40_000])
@pytest.mark.parametrize("sigma", [0.0, 0.05, 1.0])
def test_dp_noise_kernel_matches_ref(rng, n, sigma):
    x = jnp.asarray(rng.uniform(-1, 1, n).astype(np.float32))
    a = ops.dp_clip_noise(x, 0.5, sigma, SEED)
    b = ref.dp_clip_noise(x, 0.5, sigma, SEED)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_dp_noise_is_gaussian(rng):
    x = jnp.zeros(200_000, jnp.float32)
    y = np.asarray(ops.dp_clip_noise(x, 1.0, 1.0, SEED))
    assert abs(y.mean()) < 0.02
    assert abs(y.std() - 1.0) < 0.02
    # tail sanity
    assert 0.14 < (np.abs(y) > 1.0).mean() * 0.5 + 0.08 < 0.35


def test_kernel_mask_cancellation_end_to_end(rng):
    n, size = 6, 12_345
    xs = rng.uniform(-1, 1, (n, size)).astype(np.float32)
    qs = jnp.stack([ops.quantize(jnp.asarray(x)) for x in xs])
    masked = jnp.stack([ops.mask_apply(qs[i], i, n, SEED)
                        for i in range(n)])
    np.testing.assert_array_equal(ops.secure_sum(masked),
                                  ops.secure_sum(qs))


def test_u32_to_f32_matches_direct_cast(rng):
    """The int32 route Mosaic compiles rounds exactly like the direct
    uint32 -> float32 cast the jnp references use (edges + random)."""
    from repro.kernels.common import u32_to_f32
    edges = np.array([0, 1, 0xFFFF, 0x10000, 2**24 - 1, 2**24, 2**24 + 1,
                      2**24 + 3, 2**25 + 2, 2**31 - 1, 2**31, 2**31 + 1,
                      2**32 - 129, 2**32 - 128, 2**32 - 1], np.uint32)
    x = np.concatenate([edges, rng.randint(0, 2**32, 200_000,
                                           dtype=np.uint64)
                        .astype(np.uint32)])
    got = np.asarray(u32_to_f32(jnp.asarray(x)))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  x.astype(np.float32).view(np.uint32))


def test_f32_to_u32_matches_direct_cast():
    from repro.kernels.common import f32_to_u32
    codes = np.concatenate([np.arange(0, 2**16), [2**24, 2**30, 2**31 - 128]]
                           ).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(f32_to_u32(jnp.asarray(codes))),
                                  codes.astype(np.uint32))


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", False)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """No silent interpreter fallback: only the CPU backend interprets."""
    import jax
    from repro.kernels.common import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode() is interpret
