"""Vectorized cohort execution engine: run a whole cohort's local training
as ONE compiled computation.

The AzureML-style simulator (paper §5, Fig. 10) and every scale study on top
of it previously executed each client's local update in a serial Python
loop — one jit dispatch, one tiny-matmul trace per client per round. This
module stacks the cohort along a leading *client axis* (batches always;
params too, for personalized / clustered / mixed-version-async schemes) and
runs all clients' local steps with a single ``jax.vmap``-over-clients call,
optionally ``shard_map``-ed so the client axis shards over the mesh's
``data`` devices for pod-scale cohorts.

Layout conventions (leading axes):

    shared params   : leaves  (...,)                 replicated over clients
    stacked params  : leaves  (n_clients, ...)       personalized path
    stacked batches : leaves  (n_clients, local_steps, B, ...)

Three execution paths over the same ``local_update`` body (so parity is a
testable property, not an aspiration):

    serial_cohort  — python loop over per-client jitted calls (reference)
    vmap_cohort    — jit(vmap(local_update))            [default fast path]
    shard_cohort   — jit(shard_map(vmap(local_update))) [client axis over
                     the mesh's data axis; degenerates to vmap on 1 device]

``CohortEngine`` packages a ``LocalTrainSpec`` + per-client batch sampling
into the object the simulator / orchestrator consume; its ``make_trainer``
emits a paper-Fig.-3-compatible serial trainer from the SAME local_update,
which is both the migration path for existing SimClient code and the
reference the parity tests check the vectorized paths against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro import tracing  # stdlib-only; safe for core to depend on
from repro.optim.adamw import Optimizer, apply_updates


@dataclass(frozen=True)
class LocalTrainSpec:
    """What one client's local round looks like.

    loss_fn(params, batch) -> scalar; optimizer is the functional
    init/update pair from ``repro.optim``; every client runs exactly
    ``local_steps`` steps on batches of identical shape (vectorization
    requires uniform local work — ragged cohorts pad or fall back to the
    serial path).

    ``frozen``: an optional pytree every client reads but never trains
    (LoRA's base model, ``repro.core.lora.lora_spec``); the loss is then
    ``loss_fn(params, batch, frozen)``. Every execution path passes it to
    its compiled call as an ARGUMENT, broadcast over clients: a closed-over
    array would be embedded in the program as a constant, which for a
    multi-GB base neither compiles in reasonable time nor fits a program.
    """
    loss_fn: Callable
    optimizer: Optimizer
    local_steps: int = 1
    frozen: Any = None


def make_local_update(spec: LocalTrainSpec) -> Callable:
    """-> local_update(params, client_batches, frozen=None)
    -> (delta, mean_loss).

    client_batches: pytree with leaves (local_steps, B, ...); ``frozen`` is
    ``spec.frozen`` (callers pass it, so it stays an argument). The returned
    delta (new - start params, f32) is the client's pseudo-gradient payload
    in the paper's convention (strategies add it; ``launch/fl_step.py``
    negates it where a server *gradient* is expected).
    """
    opt = spec.optimizer

    def local_update(params, client_batches, frozen=None):
        def loss_fn(p, batch):
            if frozen is None:
                return spec.loss_fn(p, batch)
            return spec.loss_fn(p, batch, frozen)

        def body(carry, batch):
            p, s = carry
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            upd, s = opt.update(g, s, p)
            return (apply_updates(p, upd), s), loss

        (new_params, _), losses = jax.lax.scan(
            body, (params, opt.init(params)), client_batches)
        delta = jax.tree.map(
            lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
            new_params, params)
        return delta, jnp.mean(losses)

    return local_update


def serial_cohort(spec: LocalTrainSpec) -> Callable:
    """Reference path: one jitted per-client call, python loop over clients.

    -> f(params, stacked_batches) -> (stacked_deltas, losses (n,)).
    ``params`` leaves may carry a leading client axis (personalized) —
    detected against the batch stacking, mirroring vmap_cohort's in_axes.
    """
    one = jax.jit(make_local_update(spec))

    def run(params, stacked_batches, *, personalized=False):
        n = jax.tree.leaves(stacked_batches)[0].shape[0]
        deltas, losses = [], []
        for j in range(n):
            p_j = jax.tree.map(lambda a: a[j], params) if personalized \
                else params
            b_j = jax.tree.map(lambda a: a[j], stacked_batches)
            d, l = one(p_j, b_j, spec.frozen)
            deltas.append(d)
            losses.append(l)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        return stacked, jnp.stack(losses)

    return run


def vmap_cohort(spec: LocalTrainSpec, *, personalized: bool = False
                ) -> Callable:
    """One compiled vmap-over-clients call.

    -> f(params, stacked_batches, frozen=None) -> (stacked_deltas,
    losses (n,)); pass ``spec.frozen`` as ``frozen`` (broadcast, never
    stacked). personalized=True: params leaves carry a leading
    (n_clients,) axis.
    """
    f = jax.vmap(make_local_update(spec),
                 in_axes=(0 if personalized else None, 0, None))

    def run(params, stacked_batches, frozen=None):
        return f(params, stacked_batches, frozen)

    return jax.jit(run)


def shard_cohort(spec: LocalTrainSpec, mesh, *, axis: str = "data",
                 personalized: bool = False) -> Callable:
    """vmap_cohort with the client axis sharded over ``mesh``'s ``axis``.

    Each device traces a vmap over its n/axis_size local clients; params
    are replicated (or client-sharded when personalized). n_clients must
    divide the axis size. On a 1-device mesh this is exactly vmap_cohort.
    Same call signature as :func:`vmap_cohort`; ``frozen`` is replicated.
    """
    f = jax.vmap(make_local_update(spec),
                 in_axes=(0 if personalized else None, 0, None))
    in_specs = (P(axis) if personalized else P(), P(axis), P())
    sharded = compat.shard_map(f, mesh=mesh, in_specs=in_specs,
                               out_specs=(P(axis), P(axis)))

    def run(params, stacked_batches, frozen=None):
        return sharded(params, stacked_batches, frozen)

    return jax.jit(run)


def stack_trees(trees: list):
    """[pytree, ...] -> pytree with leading len(trees) axis (np.stack)."""
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *trees)


def _free_device_bytes(devices) -> int | None:
    """``bytes_limit - bytes_in_use`` of the fullest of ``devices``, from
    ``memory_stats()``; None where a device reports no memory (the CPU)."""
    free = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        free.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0))
    return min(free)


def unstack_tree(tree, n: int):
    """pytree with leading n axis -> [pytree, ...] of length n.

    Pulls each leaf to host ONCE before slicing — per-client slicing of
    device arrays would issue n_clients * n_leaves separate transfers,
    which dominates the whole round at simulator scale."""
    host = jax.tree.map(np.asarray, tree)
    return [jax.tree.map(lambda a: a[j], host) for j in range(n)]


class CohortEngine:
    """Batched cohort executor the simulator / orchestrator plug into.

    batch_fn(client_id, round_idx) -> pytree with leaves
    (local_steps, B, ...) — the client's local data for that round
    (deterministic in (client_id, round_idx) so serial and vectorized
    paths see identical data).

    mesh/axis select the shard_map path; mesh=None (default) uses plain
    vmap — right for CPU and single-host runs.
    """

    def __init__(self, spec: LocalTrainSpec, batch_fn: Callable,
                 template_params=None, *, mesh=None, axis: str = "data",
                 wave_size: int | None = None):
        # wave_size: stream cohorts LARGER than this through fixed-width
        # compiled waves (the last wave pads by repeating its final member
        # and the pad rows are dropped) — one compiled shape serves any
        # cohort size, bounding the compile count and the training's
        # device memory to one wave. The waves' deltas stay on the device
        # when the cohort's rows fit there beside a wave, else they go to
        # the host wave by wave, so that at 10^4-10^5-client cohorts the
        # device holds one wave (``_run_waves``). Per-client outputs are
        # bit-identical to the single-dispatch path (vmap width does not
        # change per-row float bits — the serial/vmap parity property).
        # None/0 = off.
        self.spec = spec
        self.batch_fn = batch_fn
        self.template = template_params
        self.mesh = mesh
        self.axis = axis
        self.wave_size = wave_size
        self._local = jax.jit(make_local_update(spec))
        self._fns: dict = {}
        self._wave_bytes: dict = {}   # wave shapes -> (row, footprint)

    def _cohort_fn(self, personalized: bool):
        key = bool(personalized)
        if key not in self._fns:
            if self.mesh is not None:
                self._fns[key] = shard_cohort(self.spec, self.mesh,
                                              axis=self.axis,
                                              personalized=personalized)
            else:
                self._fns[key] = vmap_cohort(self.spec,
                                             personalized=personalized)
            label = "personalized" if key else "shared"
            tracing.register_jit(f"cohort_engine.{label}", self._fns[key])
        return self._fns[key]

    # -- core entry points -------------------------------------------------

    def run_cohort(self, params, client_ids, round_idx: int):
        """Shared-params cohort -> {cid: (delta, n_samples, metrics)}.
        client_ids must be unique (one submission per client per round)."""
        out = self.run_cohort_stacked(params, client_ids, round_idx)
        return dict(zip(client_ids, self._unpack(*out)))

    def run_cohort_stacked(self, params, client_ids, round_idx: int):
        """Fused-path variant of :meth:`run_cohort`: returns
        ``(stacked_deltas, losses (n,), n_samples_per_client)`` with the
        client axis still stacked on device — feed straight into the
        vectorized privacy pipeline (``privacy_engine.aggregate_stacked`` /
        ``ManagementService.submit_cohort``) without the unstack-to-host
        round trip that ``run_cohort`` pays."""
        w = self.wave_size
        with tracing.span("local_train", n=len(client_ids),
                          round=round_idx) as sp:
            if w and len(client_ids) > w:
                return self._run_waves(params, list(client_ids),
                                       round_idx, w, sp)
            batches = self._make_batches(params, client_ids,
                                         [round_idx] * len(client_ids))
            if self.mesh is not None:
                self._check_divisible(len(client_ids))
            deltas, losses = self._cohort_fn(False)(params, batches,
                                                    self.spec.frozen)
            return deltas, losses, self._n_samples(batches, stacked=True)

    def _make_batches(self, params, client_ids, round_idxs):
        """The clients' stacked batches (one round index per client), in a
        ``make_batches`` span that counts the host bytes the cohort call
        will send: these batches and any host leaves of ``params``."""
        with tracing.span("make_batches", n=len(client_ids)) as sp:
            batches = stack_trees([self.batch_fn(cid, r)
                                   for cid, r in zip(client_ids,
                                                     round_idxs)])
            sp.transfer(h2d=(params, batches))
        return batches

    def _run_waves(self, params, client_ids, round_idx: int, w: int,
                   span):
        """Stream an oversized cohort through fixed-width ``w``-client
        waves of the shared-params executable; the short last wave pads by
        repeating its final member (pad rows dropped), so a single compiled
        shape serves every cohort size.

        Where the waves' deltas gather is decided once, before the first
        wave dispatches (:meth:`_rows_fit_on_device`), and tagged on the
        ``local_train`` span as ``rows``. ``"device"``: each wave's outputs
        stay on the device and are stacked there, with no sync between
        waves, and the caller gets device arrays that the privacy chain
        ravels in place. ``"host"``: each wave's outputs are pulled to the
        host before the next dispatches and stacked there, so the device
        holds ONE wave whatever the cohort's size."""
        if self.mesh is not None:
            self._check_divisible(w)
        fn = self._cohort_fn(False)
        on_device = None
        delta_parts, loss_parts, n_samples = [], [], None
        for s in range(0, len(client_ids), w):
            chunk = client_ids[s:s + w]
            n_real = len(chunk)
            if n_real < w:
                chunk = chunk + [chunk[-1]] * (w - n_real)
            with tracing.span("train_wave", wave=s // w, w=w,
                              n_real=n_real):
                batches = self._make_batches(params, chunk,
                                             [round_idx] * w)
                if on_device is None:
                    on_device = self._rows_fit_on_device(
                        fn, params, batches, len(client_ids))
                    span.set(rows="device" if on_device else "host")
                deltas, losses = fn(params, batches, self.spec.frozen)
                if n_samples is None:
                    n_samples = self._n_samples(batches, stacked=True)
                if on_device:
                    if n_real < w:
                        deltas = jax.tree.map(lambda a: a[:n_real], deltas)
                        losses = losses[:n_real]
                    delta_parts.append(deltas)
                    loss_parts.append(losses)
                    continue
                with tracing.span("wave_to_host") as sp:
                    sp.transfer(d2h=(deltas, losses))
                    host = jax.tree.map(np.asarray, deltas)
                    delta_parts.append(jax.tree.map(lambda a: a[:n_real],
                                                    host))
                    loss_parts.append(np.asarray(losses)[:n_real])
        with tracing.span("stack_waves") as sp:
            if on_device:
                # the parts go with this frame, as soon as the stack exists
                return (jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                     *delta_parts),
                        jnp.concatenate(loss_parts), n_samples)
            stacked = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                                   *delta_parts)
            losses = np.concatenate(loss_parts)
            sp.transfer(h2d=losses)
            return stacked, jnp.asarray(losses), n_samples

    def _rows_fit_on_device(self, fn, params, batches, n_rows: int) -> bool:
        """Whether a waved cohort's ``n_rows`` deltas may gather on the
        device: twice their bytes (the stack the caller holds, and
        ``ravel_rows``' flat copy of it that the privacy chain makes) plus
        one wave's compiled footprint (arguments, outputs, temporaries)
        must fit in what the device has free now. A row's bytes and the
        footprint come from the wave executable as compiled for these
        shapes (the one the waves then run), read once per shape. A device
        that reports no memory keeps the rows on the host."""
        devices = (self.mesh.local_devices if self.mesh is not None
                   else jax.local_devices()[:1])
        free = _free_device_bytes(devices)
        if free is None:
            return False
        leaves = jax.tree.leaves((params, batches))
        key = (jax.tree.structure((params, batches)),
               tuple((a.shape, str(a.dtype)) for a in leaves))
        if key not in self._wave_bytes:
            compiled = fn.lower(params, batches, self.spec.frozen).compile()
            mem = compiled.memory_analysis()
            w = jax.tree.leaves(batches)[0].shape[0]
            row = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(compiled.out_info[0])) // w
            self._wave_bytes[key] = (row, mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     - mem.alias_size_in_bytes
                                     + mem.temp_size_in_bytes)
        row, footprint = self._wave_bytes[key]
        shards = self.mesh.shape[self.axis] if self.mesh is not None else 1
        return 2 * n_rows * row // shards + footprint <= free

    def run_cohort_personalized(self, params_list, client_ids, round_idxs):
        """Per-client params (clustered FL branches, async mixed-version
        cohorts) -> [(delta, n_samples, metrics), ...] in input order.
        Positional because async event groups may contain the same client
        twice (a fast client re-submitting before the next server step)."""
        return self._unpack(*self.run_cohort_personalized_stacked(
            params_list, client_ids, round_idxs))

    def run_cohort_personalized_stacked(self, params_list, client_ids,
                                        round_idxs):
        """Fused-path variant of :meth:`run_cohort_personalized`: returns
        ``(stacked_deltas, losses (n,), n_samples_per_client)`` with the
        client axis still stacked on device — feed straight into the async
        bulk route (``ManagementService.submit_updates_async`` ->
        ``AsyncServer.submit_batch``) without the unstack-to-host round
        trip. Positional like its per-client twin (async event groups may
        repeat a client)."""
        with tracing.span("local_train", n=len(client_ids),
                          personalized=True):
            stacked_params = jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *params_list)
            batches = self._make_batches(params_list, client_ids,
                                         round_idxs)
            if self.mesh is not None:
                self._check_divisible(len(client_ids))
            deltas, losses = self._cohort_fn(True)(stacked_params, batches,
                                                   self.spec.frozen)
            return deltas, losses, self._n_samples(batches, stacked=True)

    # -- adapters ----------------------------------------------------------

    def make_trainer(self, client_id):
        """Paper-Fig.-3 serial trainer from the same local_update — the
        migration path for legacy SimClient code and the parity reference."""
        from repro.checkpoint import deserialize_pytree

        def trainer(blob, round_idx):
            params = deserialize_pytree(blob, like=self.template)
            b = jax.tree.map(jnp.asarray, self.batch_fn(client_id, round_idx))
            delta, loss = self._local(params, b, self.spec.frozen)
            n = self._n_samples(b, stacked=False)
            return (jax.tree.map(lambda a: np.asarray(a, np.float32), delta),
                    n, {"loss": float(loss)})

        return trainer

    # -- internals ---------------------------------------------------------

    def _check_divisible(self, n: int):
        size = self.mesh.shape[self.axis]
        if n % size:
            raise ValueError(
                f"cohort of {n} does not divide mesh axis "
                f"{self.axis!r} of size {size}")

    @staticmethod
    def _n_samples(batches, *, stacked: bool) -> int:
        leaf = jax.tree.leaves(batches)[0]
        steps, b = leaf.shape[(1 if stacked else 0):][:2]
        return int(steps) * int(b)

    def _unpack(self, deltas, losses, n_samples):
        losses = np.asarray(losses)
        return [(delta, n_samples, {"loss": float(losses[j])})
                for j, delta in enumerate(unstack_tree(deltas,
                                                       len(losses)))]
