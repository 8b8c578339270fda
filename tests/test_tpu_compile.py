"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (casts Mosaic does
not lower, tiles that do not fit VMEM, programs larger than HBM), so these
tests compile the Pallas kernels and the round's programs at real widths
for a ``v5e:2x2`` topology: the kernels at the whisper-medium LoRA payload,
the privacy chain at the paper's §5.1 shapes, one whisper-medium LoRA
client wave at Whisper's input shapes, and the per_pod ``fl_round`` on a
four-chip mesh. Nothing runs; results and times need the chip
(``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import chip_smoke
from benchmarks.bench_compression import WHISPER_LORA
from repro.kernels import mask_gen
from repro.kernels.common import LANES, ROW_BLOCK

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            desc = None
            reason = f"no v5e:2x2 topology can be described here: {e}"
        if desc is not None:
            yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()
    if desc is None:
        pytest.skip(reason)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro import compat
    return compat.make_mesh((4, 1, 1), ("pod", "data", "model"),
                            devices=topo.devices)


@pytest.fixture(scope="module")
def whisper():
    """Abstract whisper-medium base and its rank-2 attention adapters."""
    from repro.configs import get_config
    from repro.core import lora
    from repro.models import init_params

    cfg = get_config("whisper-medium")
    base = jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))
    adapters = jax.eval_shape(
        lambda k: lora.init_adapters(WHISPER_LORA, base, k),
        jax.random.PRNGKey(1))
    return cfg, base, adapters


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _payload_rows(words: int) -> int:
    """(rows, 128) tiling of a ``words``-long payload (ops.py padding)."""
    per_block = ROW_BLOCK * LANES
    return -(-words // per_block) * ROW_BLOCK


def _kernel_case(name, rows, sds):
    """-> (fn, abstract args) of one kernel at ``rows`` payload rows:
    the batched mask kernel over a 20-client cohort, the rest per client
    (VG size 8: 7 pair seeds)."""
    from repro.kernels import dp_noise, quantize, secure_sum
    u32, f32 = jnp.uint32, jnp.float32
    tile = (rows, LANES)
    return {
        "mask_apply": (
            lambda q, s: mask_gen.mask_apply_tiled(q, s, interpret=False),
            (sds(tile, u32), sds((7, 3), u32))),
        "mask_apply_batched": (
            lambda q, s: mask_gen.mask_apply_batched_tiled(
                q, s, interpret=False),
            (sds((20, *tile), u32), sds((20, 7, 3), u32))),
        "secure_sum": (
            lambda p: secure_sum.secure_sum_tiled(p, interpret=False),
            (sds((8, *tile), u32),)),
        "dp_clip_noise": (
            lambda x, c, s: dp_noise.dp_clip_noise_tiled(
                x, c, 0.08, s, interpret=False),
            (sds(tile, f32), sds((), f32), sds((2,), u32))),
        "quantize": (
            lambda x: quantize.quantize_tiled(x, 1.0, 20, interpret=False),
            (sds(tile, f32),)),
        "dequantize_sum": (
            lambda q: quantize.dequantize_sum_tiled(q, 8, 1.0, 20,
                                                    interpret=False),
            (sds(tile, u32),)),
    }[name]


@pytest.mark.parametrize("name", ["mask_apply", "mask_apply_batched",
                                  "secure_sum", "dp_clip_noise", "quantize",
                                  "dequantize_sum"])
def test_kernel_compiles_for_v5e(one_chip, whisper, name):
    """Every Pallas kernel compiles for the chip at the whisper-medium
    adapter payload (~1.2M uint32 words per client)."""
    from repro.core import lora
    rows = _payload_rows(lora.n_params(whisper[2]))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, rows, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_kernels", [True, False])
def test_cohort_interims_compiles_at_paper_shapes(one_chip, monkeypatch,
                                                  use_kernels):
    """The one-jit privacy chain (local DP -> quantize -> mask -> VG sums
    -> limb states) for the paper's 20-client round of the full-width
    bert-tiny-spam update; the kernel path carries the Mosaic kernel."""
    from repro.configs import get_config
    from repro.core import privacy_engine as pe
    from repro.core.secure_agg import SecureAggConfig
    from repro.models import classifier_init, init_params

    # the described chip is not the default backend: steer the mask
    # kernel off the CPU interpreter for this compile
    monkeypatch.setattr(mask_gen, "interpret_mode", lambda: False)
    cfg = get_config("bert-tiny-spam")
    model = jax.eval_shape(
        lambda k: {"trunk": init_params(cfg, k, max_positions=32),
                   "head": classifier_init(cfg, k)}, jax.random.PRNGKey(0))
    size = sum(a.size for a in jax.tree.leaves(model))
    args, statics = chip_smoke.cohort_interims_args(20, size, 8,
                                                    sharding=one_chip)
    compiled = pe._cohort_interims.lower(
        *args, secure_cfg=SecureAggConfig(use_kernels=use_kernels),
        dp_cfg=chip_smoke.paper_dp(), **statics).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernels
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_whisper_lora_wave_fits_one_chip(one_chip, whisper):
    """One CohortEngine wave (one client) of whisper-medium LoRA at
    Whisper's input shapes — 1,500 encoder frames, 448 decoder tokens —
    with the 3 GB frozen base passed as an argument, not a constant."""
    from repro.core import lora
    from repro.core.cohort_engine import vmap_cohort
    from repro.models import loss_fn
    from repro.optim import sgd

    cfg, base, adapters = whisper
    spec = lora.lora_spec(WHISPER_LORA, base,
                          lambda p, b: loss_fn(cfg, p, b), sgd(1e-3))
    one = chip_smoke.whisper_batch_fn(cfg, 1500, 448, 1)("client-0000", 0)
    batch = {k: jax.ShapeDtypeStruct((1, *v.shape), v.dtype,
                                     sharding=one_chip)
             for k, v in one.items()}
    compiled = vmap_cohort(spec).lower(
        _placed(adapters, one_chip), batch, _placed(base, one_chip)
    ).compile()
    mem = compiled.memory_analysis()
    base_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(base))
    assert mem.argument_size_in_bytes >= base_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.5 * V5E_HBM_BYTES


def test_per_pod_fl_round_compiles_on_four_chips(mesh4):
    """The cross-silo fl_round on a (pod=4, data=1, model=1) v5e mesh:
    stage 2 takes the shard_map pod route, whose limb merge is one
    cross-chip uint32 all-reduce."""
    from repro import compat
    from repro.configs import get_reduced_config
    from repro.launch import sharding as shd
    from repro.launch.fl_step import make_fl_train_step
    from repro.models import init_params
    from repro.optim import adamw

    cfg = get_reduced_config("deepseek-67b")
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(adamw().init, params)
    with compat.set_mesh(mesh4):
        fl_round, meta = make_fl_train_step(cfg, mesh4, secure=True,
                                            vg_size=1, microbatches=1)
        assert meta["stage2_route"] == "shard_map_pod"
        p_sh = shd.to_shardings(mesh4, shd.params_pspecs(cfg, params, mesh4))
        o_sh = shd.to_shardings(mesh4, shd.opt_pspecs(cfg, opt_state, mesh4))
        b_sh = NamedSharding(mesh4, P("pod"))
        tok = (4, 4, 16)
        batch = {"tokens": jax.ShapeDtypeStruct(tok, jnp.int32,
                                                sharding=b_sh),
                 "targets": jax.ShapeDtypeStruct(tok, jnp.int32,
                                                 sharding=b_sh),
                 "mask": jax.ShapeDtypeStruct(tok, jnp.float32,
                                              sharding=b_sh)}
        seed = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                    sharding=NamedSharding(mesh4, P()))
        compiled = jax.jit(
            fl_round, in_shardings=(p_sh, o_sh, b_sh, None),
            out_shardings=(p_sh, o_sh, None),
        ).lower(jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=s), params, p_sh),
                jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=s), opt_state, o_sh),
                batch, seed).compile()
    assert "all-reduce" in compiled.as_text()
