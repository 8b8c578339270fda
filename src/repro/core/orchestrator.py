"""Round-level FL protocol engine: what the Management Service's "task
orchestrator" role does per iteration (paper §3.1.1), with the privacy
pipeline of §4 wired in the paper's order:

  client update -> [local DP clip+noise] -> quantize -> pairwise mask
    -> stage-1 VG modular sum -> stage-2 master combine
    -> [global DP noise] -> strategy server update

The service personas (selection, auth, task state) live in ``repro.fl``;
this module is the pure protocol math so it can be tested and reused by both
the cross-device simulator and the on-pod ``launch/train.py`` path.

The async (Papaya/FedBuff) path lives in :class:`AsyncServer`: a serial
per-submission reference (``submit``) and a fused batch entry
(``submit_batch``) over the same device-resident buffer
(``strategies.FedBuff`` — see its module docstring for the buffer layout).
Parity contract: batch DP key-folds follow the global submission counter,
so N serial submits and one batch produce bit-identical buffers, weights,
and models.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp as dp_mod
from repro.core import privacy_engine as pe
from repro.core import raveling
from repro.core import secure_agg as sa
from repro.core.strategies import FedBuff
from repro.core.virtual_groups import make_virtual_groups
from repro import tracing  # stdlib-only; safe for core to depend on


@dataclass
class RoundInfo:
    round_idx: int
    n_participants: int          # survivors actually aggregated (== |S|)
    n_groups: int
    metrics: dict = field(default_factory=dict)
    n_shards: int = 1   # stage-2 combine shards (hierarchical master)
    # churn telemetry (paper §3.1.4 heterogeneity): selected cohort size,
    # mid-round dropouts, and the mask-recovery wall time
    n_selected: int = 0          # set to n_participants when nobody drops
    n_dropped: int = 0
    recovery_s: float = 0.0
    # compressed rounds: bytes per client entering secure aggregation (the
    # measured upload the ROADMAP <1%-of-model acceptance reads); 0 = dense
    upload_bytes: int = 0
    # stage-2 aggregation path this round took: "single_dispatch" / "waved"
    # / "churn_recovery" (vectorized engine) or "serial" (reference loop)
    stage2_route: str = "serial"


@dataclass
class ClientResult:
    update: Any                  # pseudo-gradient pytree
    n_samples: int
    metrics: dict = field(default_factory=dict)


def _round_randomness(key, round_seed, round_idx: int):
    key = key if key is not None else jax.random.PRNGKey(round_idx)
    if round_seed is None:
        round_seed = jax.random.key_data(
            jax.random.fold_in(jax.random.PRNGKey(17), round_idx)
        ).astype(jnp.uint32)[:2]
    return key, round_seed


def _secure_mean_serial(updates_sorted: dict, plan, round_seed, key,
                        secure_cfg, dp_cfg):
    """Bit-exact reference: per-client python loop (DP -> protect), then
    the two-stage combine. Kept verbatim as the parity oracle for the
    vectorized engine."""
    updates = {}
    for j, (cid, u) in enumerate(updates_sorted.items()):
        if dp_cfg.mechanism == "local":
            u = dp_mod.local_dp(u, dp_cfg, jax.random.fold_in(key, j))
        elif dp_cfg.mechanism == "global":
            u = dp_mod.clip_update(u, dp_cfg.clip_norm)
        updates[cid] = u
    return sa.secure_aggregate_round(updates, plan, round_seed, secure_cfg)


def _secure_mean_survivors(updates_sorted: dict, plan, round_seed, key,
                           secure_cfg, dp_cfg, fold_of: dict):
    """Churn twin of :func:`_secure_mean_serial`: ``updates_sorted`` holds
    only the survivors while ``plan`` covers the full selected cohort.
    DP keys fold at ``fold_of[cid]`` — the client's SELECTION-TIME row in
    the full sorted cohort, assigned before anyone dropped — so a
    survivor's noised update is bit-identical whether or not its peers
    survived (and matches the vectorized engine's row-indexed folds)."""
    updates = {}
    for cid, u in updates_sorted.items():
        if dp_cfg.mechanism == "local":
            u = dp_mod.local_dp(u, dp_cfg,
                                jax.random.fold_in(key, fold_of[cid]))
        elif dp_cfg.mechanism == "global":
            u = dp_mod.clip_update(u, dp_cfg.clip_norm)
        updates[cid] = u
    return sa.secure_aggregate_survivors(updates, plan, round_seed,
                                         secure_cfg)


def _compressed_secure_mean(compressor, flat_rows, cids_sorted,
                            protocol_order, plan, round_idx, round_seed,
                            key, secure_cfg, dp_cfg, n_shards, stats):
    """Sparse sync round core: compress the survivors' flat rows onto the
    round's shared support, run the UNCHANGED §4 chain on the (n, k)
    payload, then noise (global DP) and scatter the aggregated k-vector
    back to the dense domain.

    Compression precedes the privacy chain — DP clip/noise apply to the
    transmitted k-vector, the quantity that actually leaves the device —
    and is pure host numpy, so the serial reference and the vectorized /
    wave / churn engines consume bit-identical payload rows. Returns the
    dense (size,) f32 mean delta."""
    payload = compressor.compress_rows(cids_sorted,
                                       np.asarray(flat_rows, np.float32),
                                       round_idx)
    if stats is not None:
        stats["upload_bytes"] = compressor.payload_bytes()
    if list(protocol_order) == list(cids_sorted):
        if secure_cfg.vectorized:
            mean_k = pe.aggregate_flat(
                jnp.asarray(payload), plan, cids_sorted, round_seed,
                secure_cfg=secure_cfg, dp_cfg=dp_cfg, key=key,
                n_shards=n_shards, stats=stats)
        else:
            mean_k = _secure_mean_serial(
                {cid: jnp.asarray(payload[j])
                 for j, cid in enumerate(cids_sorted)}, plan, round_seed,
                key, secure_cfg, dp_cfg)
    elif secure_cfg.vectorized:
        # churn: scatter survivor payload rows into their selection-time
        # cohort rows; recovery then runs over the SPARSE interims (the
        # chain is size-agnostic — k is just a small `size`)
        pos_of = {cid: j for j, cid in enumerate(protocol_order)}
        alive = np.zeros(len(protocol_order), bool)
        full = np.zeros((len(protocol_order), payload.shape[1]),
                        np.float32)
        for j, cid in enumerate(cids_sorted):
            full[pos_of[cid]] = payload[j]
            alive[pos_of[cid]] = True
        mean_k = pe.aggregate_flat(
            jnp.asarray(full), plan, list(protocol_order), round_seed,
            secure_cfg=secure_cfg, dp_cfg=dp_cfg, key=key,
            n_shards=n_shards, alive=alive, stats=stats)
    else:
        fold_of = {cid: j for j, cid in enumerate(protocol_order)}
        mean_k = _secure_mean_survivors(
            {cid: jnp.asarray(payload[j])
             for j, cid in enumerate(cids_sorted)}, plan, round_seed, key,
            secure_cfg, dp_cfg, fold_of)
    if dp_cfg.mechanism == "global":
        # noise the aggregated k-vector (the released quantity) BEFORE
        # scattering — off-support coordinates carry no signal and get
        # no noise
        mean_k = dp_mod.global_dp(mean_k, dp_cfg, len(cids_sorted),
                                  jax.random.fold_in(key, 10_000))
    return jnp.asarray(compressor.decompress(np.asarray(mean_k),
                                             round_idx))


def run_sync_round(params, strategy, strategy_state,
                   client_results: dict,
                   *, round_idx: int, vg_size: int,
                   secure_cfg: sa.SecureAggConfig = sa.SecureAggConfig(),
                   dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig(),
                   key=None, round_seed=None, cohort=None,
                   compressor=None):
    """One synchronous FL round over a cohort of client results.

    ``secure_cfg.vectorized`` (default) runs the whole privacy pipeline —
    DP, quantize, mask, VG sums, master combine — as one compiled call via
    ``repro.core.privacy_engine``; ``vectorized=False`` keeps the serial
    per-client reference loop (bit-identical output, O(n) dispatches).
    Plans past 2^16 VGs (or with ``secure_cfg.master_shards`` set) take
    the hierarchical sharded stage-2 route on both paths — bit-identical
    at any legal shard count.

    ``cohort``: the FULL selected client list — pass it when some
    selected clients dropped mid-round (``client_results`` then holds the
    survivors only). The VG plan and the DP key-fold rows are built over
    the full cohort (clients masked/noised before drops were known), the
    dropped residual is recovered (``repro.core.dropout``), and the round
    aggregates exactly the survivor mean — no abort, bit-identical to a
    clean round over the survivors.

    ``compressor``: optional ``repro.core.sparse.TopKCompressor`` — the
    round's payload becomes the (n, k) shared-support compression of the
    survivors' flat updates (error feedback carried across rounds), fed
    through the same chain; the aggregated k-vector is noised (global DP)
    then scattered back to the dense domain before the strategy."""
    key, round_seed = _round_randomness(key, round_seed, round_idx)

    cids = sorted(client_results)
    protocol_order = sorted(cohort) if cohort is not None else cids
    dropped = [c for c in protocol_order if c not in client_results]
    if len(protocol_order) - len(dropped) != len(cids):
        raise ValueError("client_results must be a subset of cohort")
    plan = make_virtual_groups(protocol_order, vg_size, seed=round_idx)
    n_shards = sa.resolve_master_shards(len(plan.groups), secure_cfg)
    stats: dict = {}

    if compressor is not None:
        flat, unflatten = pe.stack_flat_updates(
            [client_results[c].update for c in cids])
        delta = unflatten(_compressed_secure_mean(
            compressor, flat, cids, protocol_order, plan, round_idx,
            round_seed, key, secure_cfg, dp_cfg, n_shards, stats))
    elif not dropped:
        if secure_cfg.vectorized:
            flat, unflatten = pe.stack_flat_updates(
                [client_results[c].update for c in cids])
            delta = unflatten(pe.aggregate_flat(
                flat, plan, cids, round_seed,
                secure_cfg=secure_cfg, dp_cfg=dp_cfg, key=key,
                n_shards=n_shards, stats=stats))
        else:
            delta = _secure_mean_serial(
                {cid: client_results[cid].update for cid in cids}, plan,
                round_seed, key, secure_cfg, dp_cfg)
    elif secure_cfg.vectorized:
        flat, unflatten = pe.stack_flat_updates(
            [client_results[c].update for c in cids])
        alive = np.asarray([c in client_results for c in protocol_order],
                           bool)
        full = jnp.zeros((len(protocol_order), flat.shape[1]), flat.dtype)
        positions = jnp.asarray(np.nonzero(alive)[0], jnp.int32)
        delta = unflatten(pe.aggregate_flat(
            full.at[positions].set(flat), plan, protocol_order, round_seed,
            secure_cfg=secure_cfg, dp_cfg=dp_cfg, key=key,
            n_shards=n_shards, alive=alive, stats=stats))
    else:
        fold_of = {cid: j for j, cid in enumerate(protocol_order)}
        delta = _secure_mean_survivors(
            {cid: client_results[cid].update for cid in cids}, plan,
            round_seed, key, secure_cfg, dp_cfg, fold_of)

    if dp_cfg.mechanism == "global" and compressor is None:
        # (compressed rounds noise the aggregated k-vector inside
        # _compressed_secure_mean, before the scatter)
        delta = dp_mod.global_dp(delta, dp_cfg, len(cids),
                                 jax.random.fold_in(key, 10_000))

    # DGA-style strategies may re-weight using client metrics; the secure
    # aggregate above is the privacy-preserving uniform mean, so strategies
    # that need per-client weights blend the (non-private) metric weights at
    # the interim level: we apply the strategy on the single cohort mean.
    with tracing.span("server_update", round=round_idx):
        delta = strategy.combine([delta], [1.0],
                                 [avg_metrics(client_results)])
        params, strategy_state = strategy.apply(params, strategy_state,
                                                delta)

    info = RoundInfo(round_idx, len(cids), len(plan.groups),
                     metrics=avg_metrics(client_results),
                     n_shards=n_shards,
                     n_selected=len(protocol_order),
                     n_dropped=len(dropped),
                     recovery_s=stats.get("recovery_s", 0.0),
                     upload_bytes=stats.get("upload_bytes", 0),
                     stage2_route=stats.get("stage2_route", "serial"))
    return params, strategy_state, info


def run_sync_round_stacked(params, strategy, strategy_state,
                           client_ids, stacked_updates, metrics_list=None,
                           *, round_idx: int, vg_size: int,
                           secure_cfg: sa.SecureAggConfig
                           = sa.SecureAggConfig(),
                           dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig(),
                           key=None, round_seed=None, cohort=None,
                           compressor=None):
    """Fused sync round: cohort updates arrive ALREADY STACKED (pytree
    leaves (n_clients, ...)) straight from ``CohortEngine.run_cohort_
    stacked`` — no unstack-to-host, no per-client dict round-trip. Produces
    the same round as :func:`run_sync_round` given the same cohort.

    ``metrics_list``: optional per-client metric dicts (input order) for
    the round's RoundInfo. ``cohort``: the FULL selected client list when
    ``client_ids``/``stacked_updates`` hold only the round's survivors —
    the plan spans the full cohort and the dropped residual is recovered,
    exactly as in :func:`run_sync_round`."""
    key, round_seed = _round_randomness(key, round_seed, round_idx)
    cids = list(client_ids)
    with tracing.span("vg_plan", n=len(cids)):
        order = sorted(range(len(cids)), key=cids.__getitem__)
        if order != list(range(len(cids))):
            # protocol (and DP key-fold) order is sorted-cid — reorder
            # rows with one gather per leaf rather than per client
            idx = jnp.asarray(order)
            stacked_updates = jax.tree.map(lambda a: a[idx],
                                           stacked_updates)
        cids_sorted = [cids[j] for j in order]
        protocol_order = sorted(cohort) if cohort is not None \
            else cids_sorted
        n_dropped = len(protocol_order) - len(cids_sorted)
        cohort_set = set(protocol_order)
        if n_dropped < 0 or any(c not in cohort_set for c in cids_sorted):
            raise ValueError("client_ids must be a subset of cohort")
        plan = make_virtual_groups(protocol_order, vg_size, seed=round_idx)
        n_shards = sa.resolve_master_shards(len(plan.groups), secure_cfg)
    stats: dict = {}

    if compressor is not None:
        flat = pe.ravel_rows(stacked_updates)
        template = jax.tree.map(lambda a: a[0], stacked_updates)
        _, unflatten = raveling.cached_unflatten(template)
        delta = unflatten(_compressed_secure_mean(
            compressor, flat, cids_sorted, protocol_order, plan,
            round_idx, round_seed, key, secure_cfg, dp_cfg, n_shards,
            stats))
    else:
        delta = pe.aggregate_stacked(
            stacked_updates, plan, cids_sorted, round_seed,
            secure_cfg=secure_cfg, dp_cfg=dp_cfg, key=key,
            cohort_order=protocol_order if n_dropped else None,
            stats=stats)
        if dp_cfg.mechanism == "global":
            delta = dp_mod.global_dp(delta, dp_cfg, len(cids),
                                     jax.random.fold_in(key, 10_000))

    metrics = _avg_metric_dicts(metrics_list or [])
    with tracing.span("server_update", round=round_idx):
        delta = strategy.combine([delta], [1.0], [metrics])
        params, strategy_state = strategy.apply(params, strategy_state,
                                                delta)
    info = RoundInfo(round_idx, len(cids), len(plan.groups), metrics=metrics,
                     n_shards=n_shards,
                     n_selected=len(protocol_order), n_dropped=n_dropped,
                     recovery_s=stats.get("recovery_s", 0.0),
                     upload_bytes=stats.get("upload_bytes", 0),
                     stage2_route=stats.get("stage2_route", "serial"))
    return params, strategy_state, info


def execute_cohort(engine, params, client_ids, round_idx: int,
                   *, params_per_client=None) -> dict:
    """Run a whole cohort's local training through a CohortEngine and
    return ``{cid: ClientResult}`` ready for :func:`run_sync_round`.

    ``params_per_client``: optional list of per-client param pytrees
    (clustered-FL branches / mixed-version async) — selects the engine's
    stacked-params path; otherwise ``params`` is shared by every client.
    """
    if params_per_client is not None:
        raw = engine.run_cohort_personalized(
            params_per_client, client_ids, [round_idx] * len(client_ids))
        raw = dict(zip(client_ids, raw))
    else:
        raw = engine.run_cohort(params, client_ids, round_idx)
    return {cid: ClientResult(update=u, n_samples=n, metrics=m)
            for cid, (u, n, m) in raw.items()}


def _avg_metric_dicts(metric_dicts) -> dict:
    keys = set()
    for m in metric_dicts:
        keys |= set(m)
    out = {}
    for k in keys:
        vals = [float(m[k]) for m in metric_dicts if k in m]
        if vals:
            out[k] = sum(vals) / len(vals)
    return out


def avg_metrics(client_results: dict) -> dict:
    return _avg_metric_dicts([r.metrics for r in client_results.values()])


def _dp_pad_len(k: int, buffer_size: int) -> int:
    """Batched-DP pad target for a k-row batch: the next power of two
    below one buffer, whole buffers above — O(log buffer_size) compile
    classes total, <2x padded waste."""
    if k >= buffer_size:
        return -(-k // buffer_size) * buffer_size
    p = 1
    while p < k:
        p <<= 1
    return p


class AsyncServer:
    """Papaya-style async loop (paper §4.3): no VG masking (trusted
    aggregation boundary), staleness-weighted buffer of size K.

    Two entries over the same device-resident FedBuff buffer:

    ``submit``        — the kept serial reference: ravel one update, apply
                        local DP with key ``fold_in(base, counter)``, offer
                        one row, drain on fill.
    ``submit_batch``  — the fused fast path: batched DP over all rows in
                        one jitted call (counters ``start..start+k``, the
                        SAME key-fold order the serial loop uses), rows
                        written per buffer segment with one
                        ``dynamic_update_slice`` each, draining mid-batch
                        whenever the buffer fills — bit-identical to k
                        serial ``submit`` calls in the same order
                        (tests/test_async_fused.py).
    """

    def __init__(self, params, strategy: FedBuff,
                 dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig(), seed: int = 0):
        self.params = params
        self.strategy = strategy
        self.state = strategy.init_state(params)
        self.dp_cfg = dp_cfg
        self._base_key = jax.random.PRNGKey(seed)
        self._n_submissions = 0    # DP key-fold counter, shared by both paths
        self.n_server_steps = 0

    @property
    def model_version(self) -> int:
        return self.state["model_version"]

    def _dp_sigma(self) -> float:
        return float(self.dp_cfg.noise_multiplier * self.dp_cfg.clip_norm) \
            if self.dp_cfg.noise_multiplier > 0 else 0.0

    def _step(self):
        with tracing.span("drain", step=self.n_server_steps,
                          buffer_size=self.strategy.buffer_size):
            self.params, self.state = self.strategy.drain(self.params,
                                                          self.state)
        self.n_server_steps += 1

    def submit(self, result: ClientResult, update_version: int):
        """Client pushes one pseudo-gradient. Returns True if the buffer
        drained (server step happened)."""
        flat = raveling.flat_f32(result.update)
        if self.dp_cfg.mechanism == "local":
            key = jax.random.fold_in(self._base_key, self._n_submissions)
            flat = dp_mod._flat_local_dp_jit(
                flat, key, clip_norm=float(self.dp_cfg.clip_norm),
                sigma=self._dp_sigma())
        self._n_submissions += 1
        full = self.strategy.offer_flat(flat, float(result.n_samples),
                                        update_version, self.model_version)
        if full:
            self._step()
            return True
        return False

    def submit_batch(self, stacked_flat, weights, versions) -> list:
        """Bulk entry: ``stacked_flat`` is (k, size) raveled updates in
        submission order, ``weights``/``versions`` per-row n_samples and
        update versions. Steps the server mid-batch whenever the buffer
        fills (staleness for later rows sees the bumped version, exactly
        like the serial loop). Returns the batch row indices whose
        submission completed a server step ([] if none)."""
        rows = jnp.asarray(stacked_flat, jnp.float32)
        k = rows.shape[0]
        if len(weights) != k or len(versions) != k:
            raise ValueError("weights/versions must match the batch rows")
        if self.dp_cfg.mechanism == "local":
            # pad to a BOUNDED set of shape classes (powers of two below
            # one buffer, whole buffers above) so the batched-DP jit stops
            # recompiling per batch length (the ROADMAP item) while wasted
            # clip+noise work stays < 2x (padding straight to the buffer
            # size would burn up to buffer_size extra rows on a 1-row
            # batch). Pad rows burn key-folds PAST the real counter range
            # (the counter only advances by k) and are dropped before the
            # buffer writes, so serial/batch bit-parity is untouched.
            from repro.core.strategies import _pad_rows
            rows = dp_mod.flat_local_dp_rows(
                _pad_rows(rows, _dp_pad_len(k, self.strategy.buffer_size)),
                self._base_key, self._n_submissions,
                clip_norm=float(self.dp_cfg.clip_norm),
                sigma=self._dp_sigma())
        self._n_submissions += k
        steps, i = [], 0
        while i < k:
            take = min(self.strategy.room(), k - i)
            with tracing.span("buffer_write", k=take):
                full = self.strategy.offer_rows(
                    rows[i:i + take],
                    weights[i:i + take], versions[i:i + take],
                    self.model_version)
            i += take
            if full:
                self._step()
                steps.append(i - 1)
        return steps
