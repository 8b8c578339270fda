"""End-to-end FL training driver (deliverable b's e2e example backend).

Runs REAL steps (not a dry-run) of the production fl_round on whatever
devices exist — on this CPU container use a reduced arch + host mesh:

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
        --steps 30 --global-batch 16 --seq-len 64

On a TPU slice the same entry point takes the full config + production mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, compile_cache
from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_reduced_config
from repro.configs.shapes import InputShape
from repro.data import lm_batches, lm_dataset
from repro.launch import input_specs as ispec
from repro.launch import sharding as shd
from repro.launch.fl_step import make_fl_train_step, n_silos_for
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_params
from repro.optim import adamw


def make_silo_batches(cfg, n_silos, per_silo, seq_len, seed=0):
    stream = lm_dataset(n_tokens=max(200_000, 4 * n_silos * per_silo
                                     * (seq_len + 1)),
                        vocab_size=cfg.vocab_size, seed=seed)
    it = lm_batches(stream, n_silos * per_silo, seq_len, seed=seed)
    if cfg.frontend == "vision_stub" or cfg.encoder_decoder:
        raise SystemExit("use a text arch for the LM training driver")
    while True:
        b = next(it)
        yield {k: v.reshape(n_silos, per_silo, *v.shape[1:])
               for k, v in b.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--vg-size", type=int, default=None)
    ap.add_argument("--server-lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--insecure", action="store_true")
    args = ap.parse_args(argv)
    compile_cache.configure()

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh())
    n_silos = n_silos_for(cfg, mesh)
    assert args.global_batch % n_silos == 0
    per_silo = args.global_batch // n_silos

    with compat.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = adamw().init(params)
        fl_round, meta = make_fl_train_step(
            cfg, mesh, vg_size=args.vg_size, server_lr=args.server_lr,
            secure=not args.insecure, microbatches=1)
        shape = InputShape("train", args.seq_len, args.global_batch, "train")
        p_sp = shd.params_pspecs(cfg, params, mesh)
        o_sp = shd.opt_pspecs(cfg, opt_state, mesh)
        step = jax.jit(fl_round,
                       in_shardings=(shd.to_shardings(mesh, p_sp),
                                     shd.to_shardings(mesh, o_sp),
                                     None, None),
                       out_shardings=(shd.to_shardings(mesh, p_sp),
                                      shd.to_shardings(mesh, o_sp), None))
        gen = make_silo_batches(cfg, n_silos, per_silo, args.seq_len)
        print(f"[train] {cfg.name} scheme={cfg.fl_scheme} "
              f"silos={meta['n_silos']} vg={meta['vg_size']} "
              f"mesh={dict(mesh.shape)}")
        t0 = time.time()
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(gen).items()}
            seed = jnp.asarray(
                np.random.RandomState(i).randint(0, 2**31, 2), jnp.uint32)
            params, opt_state, loss = step(params, opt_state, batch, seed)
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"[train] round {i}: loss={float(loss):.4f} "
                      f"({time.time()-t0:.1f}s)")
        if args.checkpoint:
            save_checkpoint(args.checkpoint, params, step=args.steps)
            print(f"[train] checkpoint -> {args.checkpoint}")
    return float(loss)


if __name__ == "__main__":
    main()
