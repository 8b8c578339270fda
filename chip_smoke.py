#!/usr/bin/env python3
"""Run the federated round on a TPU through the entry points users call.

    python chip_smoke.py               # phases `paper` and `whisper`, one chip
    python chip_smoke.py --four-chips  # per_pod fl_round across four chips

Phase ``paper``: the paper's §5.1 spam round (bert-tiny-spam at its config
widths, 100 clients, 20 per round, batch 8, local DP) through
``ManagementService`` + ``run_sync_simulation`` + ``CohortEngine``, once
with the Pallas mask kernel and once with the jnp masks; the two final
models must be bit-identical.

Phase ``whisper``: one federated LoRA round of whisper-medium at its
published widths and input shapes (1,500 encoder frames, 448 decoder
tokens), 4 clients streamed one at a time through ``CohortEngine`` into
``submit_cohort``.

``--four-chips``: the cross-silo ``fl_round`` on a (pod=4, data=1,
model=1) mesh, whose stage 2 is a ``shard_map`` over the pod axis, plus a
bit-identity check of that combine against the zero-padded route. It runs
only this phase.

The script needs a TPU: with none it exits non-zero before any phase. It
prints no time (it proves the path runs and is right; it measures
nothing). Every phase raises on failure. The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402


def _check(ok, what: str):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _log(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _leaves_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# --------------------------------------------------------------------------
# phase `paper`
# --------------------------------------------------------------------------

def cohort_interims_args(n_clients: int, size: int, vg_size: int,
                         sharding=None):
    """Abstract arguments and plan statics of
    ``privacy_engine._cohort_interims`` for one sync round of
    ``n_clients`` updates of ``size`` f32 each, VG plan as the
    orchestrator builds it. ``sharding`` places the abstract arguments
    (a described device for a compile without the chip)."""
    from repro.core import privacy_engine as pe
    from repro.core.secure_agg import resolve_master_shards
    from repro.core.virtual_groups import make_virtual_groups

    cids = [f"client-{i:04d}" for i in range(n_clients)]
    buckets = pe.plan_buckets(make_virtual_groups(cids, vg_size, seed=0),
                              cids)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (sds((n_clients, size), jnp.float32), sds((2,), jnp.uint32),
            sds((2,), jnp.uint32),
            tuple(sds((len(b.rows),), jnp.int32) for b in buckets),
            tuple(sds((b.n_groups,), jnp.uint32) for b in buckets))
    statics = dict(bucket_shapes=tuple((b.g, b.n_groups) for b in buckets),
                   n_shards=resolve_master_shards(
                       sum(b.n_groups for b in buckets)))
    return args, statics


def paper_dp():
    """Local DP at the paper's §5.1 setting: clip 0.5, noise scale 0.08
    (multiplier 0.16)."""
    from repro.core.dp import DPConfig
    return DPConfig(mechanism="local", clip_norm=0.5,
                    noise_multiplier=0.16, delta=1e-5)


def paper_phase(*, n_clients: int = 100, clients_per_round: int = 20,
                batch_size: int = 8, n_rounds: int = 3,
                local_steps: int = 5, vg_size: int = 8, seq_len: int = 32,
                n_train: int = 10_000, **widths) -> dict:
    """The §5.1 spam round, kernel path vs jnp path. ``widths``: SpamWorld
    width overrides (vocab, d_model, d_ff, ...); none by default, so the
    model runs at the widths of ``configs/bert_tiny_spam.py``."""
    from benchmarks.common import SpamWorld
    from repro.configs import get_config
    from repro.core import privacy_engine as pe
    from repro.core.secure_agg import SecureAggConfig
    from repro.fl import ManagementService, TaskConfig
    from repro.fl.simulator import (make_heterogeneous_clients,
                                    run_sync_simulation)

    cfg = get_config("bert-tiny-spam")
    widths = {"vocab": cfg.vocab_size, "d_model": cfg.d_model, **widths}
    world = SpamWorld(seq_len=seq_len, n_train=n_train,
                      batch_size=batch_size, n_splits=n_clients, seed=0,
                      **widths)
    engine = world.make_engine(local_steps=local_steps,
                               batch_size=batch_size)
    size = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(world.model0))
    _log("paper", layers=world.cfg.num_layers, d_model=world.cfg.d_model,
         heads=world.cfg.num_heads, d_ff=world.cfg.d_ff,
         vocab=world.cfg.vocab_size, params=size, clients=n_clients,
         per_round=clients_per_round, batch=batch_size, rounds=n_rounds)

    def run(use_kernels: bool):
        svc = ManagementService(seed=0)
        tid = svc.create_task(
            TaskConfig("spam", "spam-app", "train",
                       clients_per_round=clients_per_round,
                       n_rounds=n_rounds, vg_size=vg_size, dp=paper_dp(),
                       secure_agg=SecureAggConfig(use_kernels=use_kernels)),
            world.model0)
        clients = make_heterogeneous_clients(n_clients, world.make_trainer)
        res = run_sync_simulation(svc, tid, clients, engine=engine, seed=0)
        losses = [float(h["loss"]) for h in res.metrics_history]
        return svc.get_task(tid).model, losses

    model_k, losses_k = run(True)
    _log("paper", path="kernel", losses=losses_k)
    _check(len(losses_k) == n_rounds, f"{n_rounds} rounds ran")
    _check(np.all(np.isfinite(losses_k)), "finite losses")
    _check(not _leaves_equal(model_k, world.model0), "the model moved")
    model_j, losses_j = run(False)
    _log("paper", path="jnp", losses=losses_j)
    identical = _leaves_equal(model_k, model_j)
    _check(identical, "kernel and jnp models bit-identical")

    args, statics = cohort_interims_args(clients_per_round, size, vg_size)
    hlo = pe._cohort_interims.lower(
        *args, secure_cfg=SecureAggConfig(use_kernels=True),
        dp_cfg=paper_dp(), **statics).compile().as_text()
    out = {"losses": losses_k, "bit_identical": identical,
           "tpu_custom_call": "tpu_custom_call" in hlo}
    _log("paper", bit_identical=identical,
         tpu_custom_call=out["tpu_custom_call"])
    return out


# --------------------------------------------------------------------------
# phase `whisper`
# --------------------------------------------------------------------------

def whisper_batch_fn(cfg, enc_frames: int, dec_tokens: int, batch: int):
    """Deterministic per-client batch: one local step of ``batch``
    utterances, stub frame embeddings (B, enc_frames, d_model) and
    ``dec_tokens`` decoder tokens."""
    def batch_fn(cid, round_idx):
        r = np.random.RandomState(
            (round_idx * 7919 + int(str(cid).rsplit("-", 1)[-1])) % 2**31)
        tok = (1, batch, dec_tokens)
        return {
            "frames": (r.standard_normal((1, batch, enc_frames, cfg.d_model))
                       * 0.02).astype(np.float32),
            "tokens": r.randint(0, cfg.vocab_size, tok).astype(np.int32),
            "targets": r.randint(0, cfg.vocab_size, tok).astype(np.int32),
            "mask": np.ones(tok, np.float32),
        }
    return batch_fn


def whisper_phase(*, cfg=None, n_clients: int = 4, enc_frames: int = 1500,
                  dec_tokens: int = 448, batch: int = 1) -> dict:
    """One federated LoRA round of whisper-medium with the rank-2
    attention adapters of ``benchmarks/bench_compression.py`` (``cfg``
    overrides the published config, for a toy run)."""
    from benchmarks.bench_compression import WHISPER_LORA
    from repro.configs import get_config
    from repro.core import lora
    from repro.core.cohort_engine import (CohortEngine, stack_trees,
                                          vmap_cohort)
    from repro.core.secure_agg import SecureAggConfig
    from repro.fl import ManagementService, TaskConfig
    from repro.fl.task import SelectionCriteria
    from repro.models import init_params, loss_fn
    from repro.optim import sgd

    cfg = cfg or get_config("whisper-medium")
    base = init_params(cfg, jax.random.PRNGKey(0))
    adapters0 = lora.init_adapters(WHISPER_LORA, base, jax.random.PRNGKey(1))
    _log("whisper", enc_layers=cfg.num_encoder_layers,
         dec_layers=cfg.num_layers, d_model=cfg.d_model,
         vocab=cfg.vocab_size, base_params=lora.n_params(base),
         adapter_params=lora.n_params(adapters0), clients=n_clients,
         enc_frames=enc_frames, dec_tokens=dec_tokens, batch=batch)
    spec = lora.lora_spec(WHISPER_LORA, base,
                          lambda p, b: loss_fn(cfg, p, b), sgd(1e-3),
                          local_steps=1)
    engine = CohortEngine(spec,
                          whisper_batch_fn(cfg, enc_frames, dec_tokens, batch),
                          template_params=adapters0, wave_size=1)
    svc = ManagementService(seed=0)
    tid = svc.create_task(
        TaskConfig("whisper-lora", "asr", "finetune",
                   clients_per_round=n_clients, n_rounds=1,
                   vg_size=n_clients,
                   secure_agg=SecureAggConfig(use_kernels=True),
                   selection=SelectionCriteria(require_attestation=False)),
        adapters0)
    for i in range(n_clients):
        _check(svc.register_client(tid, f"client-{i:04d}",
                                   {"os": "linux", "n_samples": batch}),
               "client registered")
    round_idx, cohort = svc.begin_round(tid)
    cohort = sorted(cohort)
    deltas, losses, n = engine.run_cohort_stacked(svc.get_task(tid).model,
                                                  cohort, round_idx)
    losses = [float(x) for x in np.asarray(losses)]
    _check(svc.submit_cohort(tid, cohort, deltas, n,
                             [{"loss": x} for x in losses]),
           "round aggregated")
    _check(len(losses) == n_clients and np.all(np.isfinite(losses)),
           "finite losses")
    moved = not _leaves_equal(svc.get_task(tid).model, adapters0)
    _check(moved, "the adapters moved")
    stats = jax.devices()[0].memory_stats() or {}
    # the compiler's account of one wave, beside the device's peak
    wave = vmap_cohort(spec).lower(
        adapters0, stack_trees([engine.batch_fn(cohort[0], round_idx)]),
        spec.frozen).compile().memory_analysis()
    out = {"losses": losses, "adapters_moved": moved,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "wave_argument_bytes": wave.argument_size_in_bytes,
           "wave_temp_bytes": wave.temp_size_in_bytes}
    _log("whisper", **out)
    return out


# --------------------------------------------------------------------------
# --four-chips
# --------------------------------------------------------------------------

def four_chip_phase(devices, *, n_rounds: int = 3, batch: int = 4,
                    seq_len: int = 16) -> dict:
    """per_pod ``fl_round`` of the reduced deepseek-67b config on a
    (pod=4, data=1, model=1) mesh over ``devices``, and the stage-2
    shard_map combine against the zero-padded route on the same
    uint32 interims."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.configs import get_reduced_config
    from repro.launch import sharding as shd
    from repro.launch.fl_step import (hierarchical_master_combine,
                                      make_fl_train_step)
    from repro.models import init_params
    from repro.optim import adamw

    cfg = get_reduced_config("deepseek-67b")
    _check(cfg.fl_scheme == "per_pod", "deepseek-67b is per_pod")
    pods = len(devices)
    mesh = compat.make_mesh((pods, 1, 1), ("pod", "data", "model"),
                            devices=devices)
    with compat.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = adamw().init(params)
        fl_round, meta = make_fl_train_step(cfg, mesh, secure=True,
                                            vg_size=1, microbatches=1,
                                            server_lr=5e-3)
        _log("four_chips", **{k: meta[k] for k in
                              ("n_silos", "vg_size", "n_vgs",
                               "stage2_route")})
        _check(meta["stage2_route"] == "shard_map_pod",
               "stage 2 takes the shard_map pod route")
        p_sh = shd.to_shardings(mesh, shd.params_pspecs(cfg, params, mesh))
        o_sh = shd.to_shardings(mesh, shd.opt_pspecs(cfg, opt_state, mesh))
        b_sh = NamedSharding(mesh, P("pod"))
        step = jax.jit(fl_round, in_shardings=(p_sh, o_sh, b_sh, None),
                       out_shardings=(p_sh, o_sh, None))
        rng = np.random.RandomState(0)
        tok = (pods, batch, seq_len)
        data = {"tokens": rng.randint(0, cfg.vocab_size, tok),
                "targets": rng.randint(0, cfg.vocab_size, tok),
                "mask": np.ones(tok, np.float32)}
        data = {k: jax.device_put(v.astype(np.float32 if k == "mask"
                                           else np.int32), b_sh)
                for k, v in data.items()}
        silo_devices = [str(s.device) for s in
                        data["tokens"].addressable_shards]
        _log("four_chips", silo_batch_devices=silo_devices)
        _check(len(set(silo_devices)) == pods,
               "each pod's silo batch on its own chip")
        losses = []
        for i in range(n_rounds):
            seed = jnp.asarray([i, i + 1], jnp.uint32)
            params, opt_state, loss = step(params, opt_state, data, seed)
            losses.append(float(loss))
        leaf = jax.tree.leaves(params)[0]
        _log("four_chips", losses=losses,
             params_device_set=sorted(str(d) for d in
                                      leaf.sharding.device_set))
        _check(np.all(np.isfinite(losses)) and losses[-1] < losses[0],
               "loss falls over the rounds")

    # stage 2 alone: same exact per-VG interims through both routes
    bits, clip = meta["bits"], meta["clip"]
    interims = np.random.RandomState(1).randint(
        0, 2 ** bits, (pods, 64, 128)).astype(np.uint32)
    x = jax.device_put(interims, NamedSharding(mesh, P("pod")))
    shards = sorted(str(s.device) for s in x.addressable_shards)
    pod_route = jax.jit(lambda a: hierarchical_master_combine(
        a, pods, clip, bits, pod_axis="pod", mesh=mesh))(x)
    padded = jax.jit(lambda a: hierarchical_master_combine(
        a, pods, clip, bits, n_shards=pods))(x)
    identical = bool(np.array_equal(np.asarray(pod_route),
                                    np.asarray(padded)))
    _log("four_chips", interim_shard_devices=shards,
         pod_route_device_set=sorted(str(d) for d in
                                     pod_route.sharding.device_set),
         padded_device_set=sorted(str(d) for d in
                                  padded.sharding.device_set),
         stage2_bit_identical=identical)
    _check(len(set(shards)) == pods, "each pod's interim on its own chip")
    _check(identical, "shard_map stage 2 == zero-padded stage 2, bit for bit")
    return {"losses": losses, "stage2_bit_identical": identical}


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def preflight(n_chips: int) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU (no CPU
    fallback) or with fewer than ``n_chips`` of them."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); refusing to run elsewhere")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX "
                         f"found {len(devices)}")
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    _log("preflight", platform=dev.platform, kind=repr(dev.device_kind),
         count=len(devices), jax=jax.__version__, libtpu=libtpu)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the per_pod fl_round across 4 chips")
    args = ap.parse_args(argv)
    device = preflight(4 if args.four_chips else 1)
    _log("preflight", compile_cache=compile_cache.configure())
    if args.four_chips:
        four_chip_phase(jax.devices()[:4])
    else:
        paper = paper_phase()
        _check(paper["tpu_custom_call"],
               "the mask kernel compiled (tpu_custom_call in the HLO)")
        whisper_phase()
    return {"ok": True, "device": device}


if __name__ == "__main__":
    print(json.dumps(main()))
