"""Federated LoRA / adapter tuning: make the unit of aggregation small.

Cross-device FL over the repo's multi-hundred-MB configs cannot ship dense
full-model deltas (paper §2's consumer-hardware premise; ROADMAP
"Compressed updates at LLM scale"). LoRA (Hu et al. 2021) factors selected
matrix leaves W into frozen W plus a trainable low-rank delta
``scale * A @ B`` over the TRAILING two dims (A: (..., d_out, r), B:
(..., r, d_in), B zero-initialized so the initial delta is exactly zero;
leading dims broadcast, so scan-stacked layer blocks get an independent
factor per layer). Federated tuning then becomes:

  - the FROZEN BASE is broadcast once (it never changes — clients cache
    it; the task's "model" is the ADAPTERS pytree only);
  - each client trains only its adapters (``lora_spec`` passes the frozen
    base to the task's loss as an argument, so ``CohortEngine`` and every
    execution path — serial / vmap / shard_map / waves — run UNCHANGED on
    the small adapter pytree);
  - the flat vector entering ``privacy_engine.aggregate_stacked`` is the
    concatenated adapter delta, so DP clip/noise, quantize, pairwise
    masks, VG sums, limb combine, dropout recovery and streaming waves
    all compose unchanged — orders of magnitude smaller, bit-exactness
    contract intact (the chain never sees the factoring).

Adapters are a plain nested dict keyed by the target leaf's param path
("trunk/layers/3/attn/wq" style), each entry {"A": ..., "B": ...} — a
normal pytree, so checkpointing, serialization and raveling need nothing
new.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LoRAConfig:
    """``rank``: the factor dimension r. ``alpha``: LoRA's scale numerator
    (delta = (alpha / rank) * A @ B). ``min_dim``: only leaves whose
    trailing two dims are both >= this are factored (factoring a tiny
    matrix costs more than shipping it). ``include``: optional
    path-substring allowlist — e.g. ``("attn",)`` restricts adapters to
    attention projections, the classic LoRA recipe; empty = every
    eligible matrix leaf."""
    rank: int = 4
    alpha: float = 8.0
    min_dim: int = 32
    include: tuple = ()

    @property
    def scale(self) -> float:
        return float(self.alpha) / float(self.rank)


def _matrix_dims(shape):
    """The (d_out, d_in) pair a leaf factors over: its TRAILING two dims.
    Leading dims are broadcast — the repo's configs scan-stack layer
    blocks, so an attention projection is (n_layers, d_model, d_model)
    and gets an independent rank-r factor per layer."""
    return shape[-2], shape[-1]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _is_target(cfg: LoRAConfig, path_s: str, leaf) -> bool:
    shape = jnp.shape(leaf)
    if len(shape) < 2 \
            or min(_matrix_dims(shape)) < max(cfg.min_dim, 2 * cfg.rank):
        return False
    if cfg.include and not any(s in path_s for s in cfg.include):
        return False
    return True


def target_paths(cfg: LoRAConfig, params) -> list:
    """Sorted param paths that get adapters under ``cfg`` (the factoring
    is a pure function of the param STRUCTURE, so client and server agree
    without negotiation)."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return sorted(_path_str(path) for path, leaf in leaves
                  if _is_target(cfg, _path_str(path), leaf))


def init_adapters(cfg: LoRAConfig, params, key):
    """-> adapters pytree {path: {"A": (d_out, r), "B": (r, d_in)}}.

    A ~ N(0, 1/r) scaled (the standard init), B = 0 — so ``merge`` at
    init returns the base bit-for-bit and the first round's adapter
    delta is a true pseudo-gradient from zero."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    by_path = {_path_str(p): leaf for p, leaf in leaves}
    adapters = {}
    for i, path_s in enumerate(target_paths(cfg, params)):
        shape = jnp.shape(by_path[path_s])
        lead, (d_out, d_in) = shape[:-2], _matrix_dims(shape)
        k = jax.random.fold_in(key, i)
        adapters[path_s] = {
            "A": (jax.random.normal(k, (*lead, d_out, cfg.rank),
                                    jnp.float32) / np.sqrt(cfg.rank)),
            "B": jnp.zeros((*lead, cfg.rank, d_in), jnp.float32),
        }
    if not adapters:
        raise ValueError("no LoRA-eligible leaves: every matrix param is "
                         f"smaller than min_dim={cfg.min_dim} (or the "
                         f"include filter {cfg.include} matched nothing)")
    return adapters


def merge(cfg: LoRAConfig, base_params, adapters):
    """Base + adapters -> effective params (W + scale * A @ B at adapter
    paths, base leaves passed through untouched — gradients w.r.t. the
    adapters flow through the addition, the base stays frozen)."""
    scale = cfg.scale

    def leaf(path, w):
        ab = adapters.get(_path_str(path))
        if ab is None:
            return w
        return (w.astype(jnp.float32)
                + scale * (ab["A"] @ ab["B"])).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(leaf, base_params)


def lora_spec(cfg: LoRAConfig, base_params, loss_fn, optimizer,
              local_steps: int = 1):
    """``LocalTrainSpec`` whose trainable params ARE the adapters pytree:
    the frozen base rides as the spec's ``frozen`` argument (passed to the
    compiled call, broadcast over clients) and merges per call, so
    ``CohortEngine`` (and the whole sync/async/churn machinery behind it)
    runs verbatim on the small adapter tree."""
    from repro.core.cohort_engine import LocalTrainSpec

    def adapter_loss(adapters, batch, base):
        return loss_fn(merge(cfg, base, adapters), batch)

    return LocalTrainSpec(loss_fn=adapter_loss, optimizer=optimizer,
                          local_steps=local_steps, frozen=base_params)


def n_params(tree) -> int:
    """Total element count of a pytree (the upload-accounting primitive:
    ``4 * n_params(adapters) / (4 * n_params(base))`` is the sync round's
    upload fraction before any top-k on the adapter vector)."""
    return int(sum(int(np.prod(jnp.shape(leaf)) or 1)
                   for leaf in jax.tree.leaves(tree)))


def upload_fraction(cfg: LoRAConfig, params) -> float:
    """Adapter-bytes / dense-bytes for ``params`` under ``cfg`` WITHOUT
    materializing the adapters (works on abstract ShapeDtypeStructs, so
    the <1%-of-model acceptance check runs against the real config's
    shapes for free)."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    dense = adapter = 0
    for path, leaf in leaves:
        shape = jnp.shape(leaf)
        dense += int(np.prod(shape) or 1)
        if _is_target(cfg, _path_str(path), leaf):
            d_out, d_in = _matrix_dims(shape)
            adapter += int(np.prod(shape[:-2]) or 1) \
                * cfg.rank * (d_out + d_in)
    if dense == 0:
        raise ValueError("empty params pytree")
    return adapter / dense
