"""A cell: one configuration under one traffic mix, built on the program's
own entry points.

The round the window drives (``Cell.run_round``) is the engine path of the
program's ``run_sync_simulation``, minus its virtual clock:

1. ``ManagementService.begin_round`` selects the cohort;
2. ``ManagementService.model_snapshot`` serialises the model;
3. the client side runs ``deserialize_pytree`` and
   ``CohortEngine.run_cohort_stacked`` (local training, vmapped, in waves
   where the cell sets a wave size);
4. ``ManagementService.submit_cohort`` runs the privacy chain (local DP,
   quantize, KDF masks, virtual-group sums, limb combine) and the server
   update, and the round ends with ``block_until_ready`` on the new model.

Each step sits in a benchmark span (``Spans``): host time by the host
clock, and a ``jax.profiler.TraceAnnotation`` on the device trace's clock.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import defaultdict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import tasks, traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    """``configs/<name>.json`` or ``workloads/<name>.json``."""
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no {kind[:-1]} named {name!r} "
                         f"({os.path.relpath(path, HERE)} is missing)")
    with open(path) as f:
        return json.load(f)


class Spans:
    """Host time per benchmark span, summed over the rounds recorded, and
    the same spans on the profiler's clock."""

    def __init__(self):
        self.total_s = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            yield
        self.total_s[name] += time.perf_counter() - t0

    def reset(self):
        self.total_s.clear()


def init_weights(shapes, key):
    """Weights from the seed, in the structure ``shapes`` gives (leaf
    shapes only; no value of the program's own initializer is used):
    LayerNorm scales 1, biases 0, embedding tables N(0, 0.02^2), matrices
    N(0, 1/fan_in), LoRA ``A`` N(0, 1/rank) and ``B`` 0. Call under
    ``jax.jit`` so that they are made on the device in one program."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "scale":
            out.append(jnp.ones(s.shape, s.dtype))
        elif name == "B" or name.startswith("b") or len(s.shape) < 2:
            out.append(jnp.zeros(s.shape, s.dtype))
        elif name in ("tok", "pos"):
            out.append(0.02 * jax.random.normal(k, s.shape, s.dtype))
        elif name == "A":
            out.append(jax.random.normal(k, s.shape, s.dtype)
                       / np.sqrt(s.shape[-1]))
        else:
            out.append(jax.random.normal(k, s.shape, s.dtype)
                       / np.sqrt(s.shape[-2]))
    return jax.tree_util.tree_unflatten(treedef, out)


def arch_config(model: dict):
    """The program's ``ArchConfig`` for a configuration's ``model`` block:
    the named preset with the block's widths."""
    from repro.configs import get_config
    fields = {k: v for k, v in model.items()
              if k not in ("arch", "max_positions")}
    return get_config(model["arch"]).replace(**fields)


@dataclass
class Cell:
    name: str
    config: dict
    workload: dict
    seed: int
    svc: object = None
    tid: int = 0
    engine: object = None
    traffic: object = None
    template: object = None
    frozen: object = None
    state0: object = None          # trainable tree at round 0 (host)
    spans: Spans = field(default_factory=Spans)
    meta: dict = field(default_factory=dict)

    @property
    def kind(self) -> str:
        return self.config["fl"]["task"]

    def recipe(self) -> dict:
        """What the reference needs of the round: optimizer, steps, DP and
        the chain's code."""
        fl, data = self.config["fl"], self.workload["data"]
        return {**fl, "local_steps": data["local_steps"],
                "chain": {"clip": self.meta["chain_clip"],
                          "bits": self.meta["chain_bits"]}}

    def model_now(self):
        return self.svc.get_task(self.tid).model

    def close(self):
        """Drop the service and the engine."""
        self.svc = self.engine = None

    def dropped(self, round_idx: int, cohort: list) -> set:
        """The cohort members that drop out of this round: the workload's
        ``drop_per_round`` of them, drawn from the seed and the round."""
        k = int(self.workload["task"].get("drop_per_round", 0))
        if not k:
            return set()
        (word,) = traffic_mod.seed_words(self.seed, 1, stream=2)
        rng = np.random.RandomState((word + round_idx) % 2**31)
        return set(rng.choice(sorted(cohort), size=k, replace=False))

    def run_round(self):
        """One round through the service; returns ``(round_idx, cohort,
        survivors, per-survivor losses)``. Dropped members are reported
        through ``report_dropout`` and the survivors submit."""
        from repro.checkpoint import deserialize_pytree
        sp = self.spans
        with sp("begin_round"):
            r, cohort = self.svc.begin_round(self.tid)
        if not cohort:
            raise RuntimeError(f"round {r}: no cohort selected")
        dropped = self.dropped(r, cohort)
        survivors = [c for c in cohort if c not in dropped]
        with sp("model_snapshot"):
            blob = self.svc.model_snapshot(self.tid)
        with sp("deserialize"):
            params = deserialize_pytree(blob, like=self.template)
        with sp("local_train"):
            stacked, losses, n = self.engine.run_cohort_stacked(
                params, survivors, r)
        with sp("losses_to_host"):
            losses = np.asarray(losses)
        with sp("submit_cohort"):
            for cid in sorted(dropped):
                self.svc.report_dropout(self.tid, cid)
            ok = self.svc.submit_cohort(self.tid, survivors, stacked, n,
                                        [{"loss": float(x)} for x in losses])
        if not ok:
            raise RuntimeError(f"round {r}: submit_cohort refused the cohort")
        with sp("block_until_ready"):
            jax.block_until_ready(self.model_now())
        return r, list(cohort), survivors, losses


def build(name: str, seed: int, *, config: dict | None = None,
          workload: dict | None = None) -> Cell:
    """Make the cell's data, weights, task and engine from the seed.
    ``config``/``workload`` override the files (tests shrink widths)."""
    from repro.core.cohort_engine import CohortEngine
    from repro.core.dp import DPConfig
    from repro.core.secure_agg import SecureAggConfig
    from repro.fl import ManagementService, TaskConfig
    from repro.fl.task import SelectionCriteria

    workload = workload or load_json("workloads", name)
    config = config or load_json("configs", workload["config"])
    cell = Cell(name, config, workload, seed)
    model, fl, task = config["model"], config["fl"], workload["task"]
    cfg = arch_config(model)
    w_model, w_svc, w_pop = traffic_mod.seed_words(seed, 3)
    key = jax.random.PRNGKey(w_model % 2**31)

    spec, model0, cell.frozen = tasks.load(fl["task"]).build(cfg, config,
                                                             workload, key)
    cell.traffic = traffic_mod.make_traffic(fl["task"], workload["data"],
                                            model, seed)
    cell.engine = CohortEngine(spec, cell.traffic.batch_fn,
                               template_params=model0,
                               wave_size=task.get("wave_size") or None)
    dp = DPConfig(**fl["dp"]) if fl["dp"]["mechanism"] != "off" else DPConfig()
    secure = SecureAggConfig(wave_clients=int(task.get("wave_clients") or 0))
    cell.meta.update(chain_clip=float(secure.clip),
                     chain_bits=int(secure.bits),
                     params=int(sum(np.prod(a.shape)
                                    for a in jax.tree.leaves(model0))))
    cell.svc = ManagementService(seed=w_svc % 2**31)
    cell.tid = cell.svc.create_task(
        TaskConfig("chipbench", name, "train",
                   clients_per_round=int(task["clients_per_round"]),
                   n_rounds=10**9, vg_size=int(fl["vg_size"]), dp=dp,
                   secure_agg=secure,
                   selection=SelectionCriteria(require_attestation=False)),
        model0)
    info = {"os": "linux", "n_samples": 100, "battery": 1.0}
    if task.get("fleet"):
        from repro.fl.population import PopulationArrays
        n = cell.svc.register_fleet(
            cell.tid, PopulationArrays.sample(int(task["clients"]),
                                              seed=w_pop % 2**31), info)
    else:
        n = sum(cell.svc.register_client(cell.tid, f"client-{i:04d}", info)
                for i in range(int(task["clients"])))
    if n != int(task["clients"]):
        raise RuntimeError(f"{n} of {task['clients']} clients enrolled")
    cell.template = model0
    cell.state0 = jax.tree.map(np.asarray, model0)
    return cell
