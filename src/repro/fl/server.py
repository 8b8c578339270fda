"""Management Service (paper §3.1.1): the user-interface API (create /
manage / monitor tasks) and the task orchestrator (advertise to Selection,
drive Secure/Master aggregation, track progress).

Task state is an in-process store (production: Redis); the aggregation math
is ``repro.core`` — this layer only sequences it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import dp as dp_mod
from repro.core import privacy_engine as pe
from repro.core.orchestrator import (AsyncServer, ClientResult,
                                     run_sync_round, run_sync_round_stacked)
from repro.core.secure_agg import AggregationRefused
from repro.core.strategies import FedBuff, make_strategy
from repro.fl.auth import AuthenticationService
from repro.fl.directory import DeviceDirectory
from repro.fl.registry import ModelRegistry
from repro.fl.selection import SelectionService
from repro.fl.task import TaskConfig, TaskRecord, TaskStatus
from repro.fl.telemetry import MetricsRegistry, MetricsStore
from repro.checkpoint import deserialize_pytree, serialize_pytree


class PermissionError_(Exception):
    pass


def _churn_metrics(info) -> dict:
    """Per-round churn telemetry from a RoundInfo: selection/survival/
    dropout counts always, mask-recovery wall time when anyone dropped."""
    out = {"n_selected": info.n_selected, "n_survived": info.n_participants,
           "n_dropped": info.n_dropped}
    if info.n_dropped:
        out["recovery_s"] = info.recovery_s
    if info.upload_bytes:
        out["upload_bytes_per_client"] = info.upload_bytes
    return out


def _model_size(model) -> int:
    """Flat coordinate count of a model pytree (the compressor's domain)."""
    import numpy as np
    return int(sum(int(np.prod(jnp.shape(leaf)) or 1)
                   for leaf in jax.tree.leaves(model)))


@dataclass
class _RoundCollector:
    round_idx: int
    cohort: list
    results: dict = field(default_factory=dict)
    dropped: set = field(default_factory=set)

    def complete(self):
        """Every cohort member accounted for — submitted OR dropped. A
        straggling VG no longer blocks the round: once the stragglers are
        reported dropped, aggregation proceeds over the survivors with
        mask recovery."""
        return set(self.results) | self.dropped >= set(self.cohort)


class ManagementService:
    def __init__(self, seed: int = 0,
                 directory: DeviceDirectory | None = None):
        self.auth = AuthenticationService()
        # the shared physical fleet; inject one directory into several
        # services (or, normally, several tasks into ONE service under a
        # ControlPlane) to make leases mutually exclusive across tasks
        self.directory = directory if directory is not None \
            else DeviceDirectory()
        self.selection = SelectionService(self.auth, seed=seed,
                                          directory=self.directory)
        self.metrics = MetricsStore()
        self.meters = MetricsRegistry()
        # flight recorder (per-task JSONL round transcripts); None = off.
        # The CLI run path installs one next to the session file
        self.flight: tracing.FlightRecorder | None = None
        # jit-cache watermark for the per-round jit_cache_misses delta
        self._jit_snapshot = tracing.jit_cache_total()
        self.registry = ModelRegistry()
        self._tasks: dict[int, TaskRecord] = {}
        self._strategies: dict[int, Any] = {}
        self._strategy_state: dict[int, Any] = {}
        self._collectors: dict[int, _RoundCollector] = {}
        self._async: dict[int, AsyncServer] = {}
        self._accountants: dict[int, dp_mod.RdpAccountant] = {}
        # task_id -> TopKCompressor (tasks with compression.kind != "none");
        # holds the per-client error-feedback residuals, so it must live as
        # long as the task
        self._compressors: dict[int, Any] = {}

    # ------------------------------------------------------------------
    # user-interface API (dashboard / CLI)
    # ------------------------------------------------------------------

    def create_task(self, config: TaskConfig, initial_model,
                    user: str = "default-user", deploy: bool = True) -> int:
        """Create a task: CREATED, then (by default) deployed to RUNNING.

        ``deploy=False`` leaves the task in CREATED — the control-plane
        lifecycle, where :meth:`deploy_task` is the explicit transition to
        RUNNING (the ``ControlPlane`` creates tasks this way). The default
        keeps the one-call convenience path for single-task use.

        The task id is derived from the service's own task store (max +
        1), NOT the module-global counter in ``fl.task`` — that counter
        resets in every fresh process, so a CLI session reloaded from disk
        would mint ids that silently overwrite persisted tasks."""
        config.owner = user
        rec = TaskRecord(config=config, model=initial_model,
                         task_id=max(self._tasks, default=0) + 1)
        self._tasks[rec.task_id] = rec
        kw = dict(config.strategy_kwargs)
        if config.mode == "async":
            strategy = FedBuff(buffer_size=config.buffer_size, **kw)
            self._async[rec.task_id] = AsyncServer(
                initial_model, strategy, config.dp)
        else:
            strategy = make_strategy(config.strategy, **kw)
            self._strategy_state[rec.task_id] = strategy.init_state(
                initial_model)
        self._strategies[rec.task_id] = strategy
        comp = config.compression.make_compressor(_model_size(initial_model))
        if comp is not None:
            self._compressors[rec.task_id] = comp
        if config.dp.mechanism != "off":
            self._accountants[rec.task_id] = dp_mod.RdpAccountant(
                config.dp, sample_rate=1.0)  # rate set per round below
        if deploy:
            self.deploy_task(rec.task_id, user=user)
        return rec.task_id

    def deploy_task(self, task_id: int, user: str = "default-user"):
        """CREATED -> RUNNING. The explicit lifecycle step between task
        definition and the scheduler granting it rounds."""
        self._check_perm(task_id, user)
        rec = self._tasks[task_id]
        if rec.status is not TaskStatus.CREATED:
            raise ValueError(f"task {task_id} is {rec.status.value}, "
                             "only CREATED tasks can be deployed")
        rec.status = TaskStatus.RUNNING

    def get_task(self, task_id: int) -> TaskRecord:
        return self._tasks[task_id]

    def list_tasks(self, app_name=None, workflow_name=None):
        tasks = list(self._tasks.values())
        if app_name is not None:
            tasks = [t for t in tasks if t.config.app_name == app_name]
        if workflow_name is not None:
            tasks = [t for t in tasks
                     if t.config.workflow_name == workflow_name]
        return tasks

    def _check_perm(self, task_id: int, user: str):
        if not self._tasks[task_id].can_manage(user):
            raise PermissionError_(f"user {user!r} cannot manage {task_id}")

    def _abort_round(self, task_id: int):
        """Discard any in-flight round: drop the collector and release
        every device lease so other tasks can select them immediately —
        pausing/cancelling one task must never pin fleet capacity."""
        rec = self._tasks[task_id]
        self._collectors.pop(task_id, None)
        self.selection.reset_round(rec)

    def pause_task(self, task_id: int, user="default-user"):
        self._check_perm(task_id, user)
        self._tasks[task_id].status = TaskStatus.PAUSED
        self._abort_round(task_id)   # round is re-selected on resume

    def resume_task(self, task_id: int, user="default-user"):
        self._check_perm(task_id, user)
        self._tasks[task_id].status = TaskStatus.RUNNING

    def cancel_task(self, task_id: int, user="default-user"):
        self._check_perm(task_id, user)
        self._tasks[task_id].status = TaskStatus.CANCELLED
        self._abort_round(task_id)

    def epsilon(self, task_id: int):
        acc = self._accountants.get(task_id)
        return acc.epsilon() if acc else None

    # ------------------------------------------------------------------
    # client-facing API (via the SDK)
    # ------------------------------------------------------------------

    def register_client(self, task_id: int, client_id: str, device_info: dict,
                        certificate=None, profile=None) -> bool:
        """``profile``: optional ``population.DeviceProfile`` recorded in
        the shared device directory (availability windows, dropout hazard
        — physical facts, shared by every task the device enrolls in)."""
        return self.selection.register(self._tasks[task_id], client_id,
                                       device_info, certificate,
                                       profile=profile)

    def register_fleet(self, task_id: int, population,
                       device_info: dict | None = None) -> int:
        """Bulk-enroll a ``population.PopulationArrays`` fleet into a task
        — the 10^6-device registration path (criteria evaluated once
        against the shared ``device_info`` template; see
        ``SelectionService.register_fleet``). Returns the enrolled count."""
        return self.selection.register_fleet(self._tasks[task_id],
                                             population,
                                             device_info=device_info)

    def model_snapshot(self, task_id: int) -> bytes:
        model = self._tasks[task_id].model
        with tracing.span("snapshot", task=task_id) as sp:
            sp.transfer(d2h=model)
            return serialize_pytree(model)

    def submit_update(self, task_id: int, client_id: str, update,
                      n_samples: int, metrics=None,
                      update_version: int | None = None) -> bool:
        """Returns True if this submission completed a server step.

        ``update_version``: the model version the client's update was
        trained FROM (async mode) — FedBuff discounts by the staleness
        ``round_idx - update_version``. Omitted => assumed current (no
        discount), which is only right for clients that fetched the
        snapshot just before training.
        """
        rec = self._tasks[task_id]
        if rec.status is not TaskStatus.RUNNING:
            return False
        result = ClientResult(update=update, n_samples=n_samples,
                              metrics=metrics or {})
        if rec.config.mode == "async":
            server = self._async[task_id]
            comp = self._compressors.get(task_id)
            if comp is not None:
                # trusted aggregation boundary (no masks): true per-client
                # top-k — the wire carries (indices, values), the buffer
                # gets the dense scatter (its math is support-agnostic)
                import numpy as np
                from repro.core import raveling
                _, _, dense = comp.compress_topk(
                    client_id, np.asarray(raveling.flat_f32(update)))
                result = ClientResult(update=jnp.asarray(dense),
                                      n_samples=n_samples,
                                      metrics=metrics or {})
            stepped = server.submit(
                result,
                update_version=rec.round_idx if update_version is None
                else update_version)
            if stepped:
                rec.model = server.params
                rec.round_idx += 1
                self._finish_round(rec, self._async_metrics(rec, server))
            return stepped
        coll = self._collectors.get(task_id)
        if coll is None or client_id not in coll.cohort \
                or client_id in coll.dropped:
            return False
        coll.results[client_id] = result
        self.selection.mark(rec, client_id, "done")   # lifecycle: submitted
        if coll.complete():
            self._run_sync_aggregation(rec, coll)
            return True
        return False

    def report_dropout(self, task_id: int, client_id: str) -> bool:
        """A selected client disconnected (or blew the round deadline)
        mid-round. Its virtual group's pairwise masks no longer cancel;
        the round proceeds anyway — aggregation runs over the survivors
        with the dropped residual recovered (``repro.core.dropout``).
        Returns True if this report CLOSED the round: aggregated over the
        survivors, or voided it (every member dropped — the round index is
        not consumed and the next ``begin_round`` re-selects)."""
        with tracing.span("report_dropout", task=task_id):
            rec = self._tasks[task_id]
            coll = self._collectors.get(task_id)
            if coll is None or client_id not in coll.cohort \
                    or client_id in coll.dropped \
                    or client_id in coll.results:
                return False
            coll.dropped.add(client_id)
            self.selection.drop(rec, client_id)
            if coll.complete():
                if coll.results:
                    self._run_sync_aggregation(rec, coll)
                else:
                    # every member dropped: void the round (no survivors
                    # to aggregate); dropped members re-enter the pool at
                    # the next begin_round
                    self._void_round(rec, coll)
                return True
            return False

    def backfill_round(self, task_id: int, unavailable, available=None
                       ) -> list:
        """Pre-training cohort repair: ``unavailable`` members (selected
        but outside their availability window before training started)
        are RELEASED — they never entered the protocol, so no masks to
        recover and no dropout on their record — and replacements are
        drawn from the pool, topping the cohort back up toward its
        selection target. Returns the repaired cohort list. Must run
        before any member submits (the VG plan spans the final cohort)."""
        rec = self._tasks[task_id]
        coll = self._collectors.get(task_id)
        if coll is None:
            return []
        if coll.results or coll.dropped:
            raise ValueError("backfill_round must run before training "
                             "starts (submissions or dropouts already "
                             "recorded)")
        unavailable = [c for c in unavailable if c in coll.cohort]
        with tracing.span("backfill", task=task_id,
                          n_released=len(unavailable)) as sp:
            released = set(unavailable)
            for cid in unavailable:
                self.selection.release(rec, cid)
            cohort = [c for c in coll.cohort if c not in released]
            # the released members are back in the pool but must not be
            # drawn straight back into the cohort they were just removed
            # from
            refill = self.selection.backfill(
                rec, len(coll.cohort) - len(cohort),
                available=lambda cid: cid not in released
                and (available is None or available(cid)))
            sp.set(n_refilled=len(refill))
        coll.cohort = sorted(cohort + refill)
        return list(coll.cohort)

    def submit_cohort(self, task_id: int, client_ids, stacked_updates,
                      n_samples: int, metrics_list=None) -> bool:
        """Bulk sync submission — the fused fast path: the WHOLE cohort's
        updates arrive stacked along the client axis (pytree leaves
        (n_clients, ...)), straight from ``CohortEngine.run_cohort_
        stacked``, and flow into the vectorized privacy pipeline without
        ever being unstacked to per-client host copies. Completes the
        round; returns True iff the round ran.

        ``n_samples`` (per client) is telemetry only: the secure aggregate
        is the privacy-preserving UNIFORM mean on both the bulk and
        per-client paths (sample-weighting would leak per-client counts
        through the aggregate).

        With churn, ``client_ids``/``stacked_updates`` hold the round's
        SURVIVORS; every other cohort member must already be reported via
        :meth:`report_dropout` — the VG plan spans the full cohort and the
        dropped residual is recovered."""
        rec = self._tasks[task_id]
        if rec.status is not TaskStatus.RUNNING or rec.config.mode == "async":
            return False
        coll = self._collectors.get(task_id)
        cids = list(client_ids)
        if coll is None or len(set(cids)) != len(cids) \
                or set(cids) != set(coll.cohort) - coll.dropped \
                or not cids:
            return False
        strategy = self._strategies[task_id]
        state = self._strategy_state[task_id]
        metrics_list = metrics_list or [{} for _ in cids]
        try:
            with tracing.span("aggregate", task=task_id,
                              round=coll.round_idx) as agg_sp:
                rec.model, state, info = run_sync_round_stacked(
                    rec.model, strategy, state, cids, stacked_updates,
                    metrics_list,
                    round_idx=coll.round_idx, vg_size=rec.config.vg_size,
                    secure_cfg=rec.config.secure_agg,
                    dp_cfg=rec.config.dp,
                    cohort=list(coll.cohort) if coll.dropped else None,
                    compressor=self._compressors.get(task_id))
        except AggregationRefused:
            self._void_round(rec, coll, reason="aggregation_refused")
            return True
        self._strategy_state[task_id] = state
        for cid in cids:
            self.selection.mark(rec, cid, "done")
        # the round is closed — drop the collector so a straggling
        # per-client submit_update cannot re-trigger aggregation
        self._collectors.pop(task_id, None)
        rec.round_idx += 1
        self._record_flight(rec, coll, info, agg_sp, survivors=cids)
        self._finish_round(rec, dict(info.metrics, n=info.n_participants,
                                     n_groups=info.n_groups,
                                     n_shards=info.n_shards,
                                     n_samples_per_client=n_samples,
                                     stage2_route=info.stage2_route,
                                     **_churn_metrics(info)))
        return True

    def submit_updates_async(self, task_id: int, client_ids,
                             stacked_updates, n_samples, update_versions,
                             metrics_list=None) -> list:
        """Bulk async submission — the fused fast path mirroring
        ``submit_cohort``: a whole event group's updates arrive stacked
        along the client axis (pytree leaves (k, ...)) straight from
        ``CohortEngine.run_cohort_personalized_stacked``, are raveled on
        device, run through the batched local-DP rows, and land in the
        FedBuff device buffer with one write per buffer segment — no
        unstack-to-host, no per-client submit round trips. Bit-identical
        to k ``submit_update`` calls in the same order.

        ``n_samples``: per-row list (or one int for all rows);
        ``update_versions``: per-row model versions the updates were
        trained FROM. ``metrics_list`` is accepted for API symmetry with
        ``submit_cohort`` — async aggregation is metrics-blind, exactly
        like the per-client path. Returns the batch row indices that
        completed a server step ([] if none, or if the task is not an
        async RUNNING task)."""
        rec = self._tasks[task_id]
        if rec.status is not TaskStatus.RUNNING \
                or rec.config.mode != "async":
            return []
        server = self._async[task_id]
        cids = list(client_ids)
        rows = pe.ravel_rows(stacked_updates)
        comp = self._compressors.get(task_id)
        if comp is not None and rows.shape[0] == len(cids):
            # compress in submission order so the residual evolution is
            # bit-identical to len(cids) per-client submit_update calls
            import numpy as np
            host = np.asarray(rows, np.float32)
            rows = jnp.asarray(np.stack(
                [comp.compress_topk(cid, host[j])[2]
                 for j, cid in enumerate(cids)]))
        if rows.shape[0] != len(cids):
            # a shape/id mismatch is a caller bug, not a rejected
            # submission — dropping the group silently would corrupt the
            # run (the sync twin escalates the same way via the
            # simulator's RuntimeError guard)
            raise ValueError(
                f"stacked updates have {rows.shape[0]} rows for "
                f"{len(cids)} client ids")
        k = len(cids)
        weights = [float(n) for n in (n_samples if isinstance(
            n_samples, (list, tuple)) else [n_samples] * k)]
        versions = [int(v) for v in update_versions]
        # serial parity at the completion boundary: the per-client loop
        # rejects every submission after the task COMPLETES, so cap the
        # batch at the rows that fit before the final server step
        steps_left = rec.config.n_rounds - rec.round_idx
        cap = (server.strategy.room()
               + (steps_left - 1) * server.strategy.buffer_size)
        if k > cap:
            rows, weights, versions = rows[:cap], weights[:cap], \
                versions[:cap]
        steps = server.submit_batch(rows, weights, versions)
        for _ in steps:
            rec.model = server.params
            rec.round_idx += 1
            self._finish_round(rec, self._async_metrics(rec, server))
        return steps

    def _async_metrics(self, rec: TaskRecord, server: AsyncServer) -> dict:
        out = {"n": server.strategy.buffer_size}
        comp = self._compressors.get(rec.task_id)
        if comp is not None:
            out["upload_bytes_per_client"] = comp.payload_bytes(
                with_indices=True)
        return out

    def async_buffer_room(self, task_id: int) -> int:
        """Submissions until the next async server step (>= 1). Sync tasks
        report 1 (every cohort submission may complete the round)."""
        server = self._async.get(task_id)
        if server is None:
            return 1
        return max(1, server.strategy.room())

    # ------------------------------------------------------------------
    # orchestration
    # ------------------------------------------------------------------

    def begin_round(self, task_id: int, available=None):
        """Select the cohort for the next round -> (round_idx, cohort).

        Over-provisions by ``config.overprovision`` and records
        ``config.round_timeout_s`` as the round deadline; ``available`` is
        an optional ``cid -> bool`` availability predicate (device windows
        at selection time)."""
        rec = self._tasks[task_id]
        if rec.status is not TaskStatus.RUNNING:
            return rec.round_idx, []
        with tracing.span("begin_round", task=task_id, round=rec.round_idx):
            self.selection.reset_round(rec)   # last round's selected/done
            with tracing.span("selection", task=task_id,
                              round=rec.round_idx) as sp:
                cohort = self.selection.select_cohort(
                    rec, overprovision=rec.config.overprovision,
                    deadline=rec.config.round_timeout_s,
                    available=available)
                sp.set(n_cohort=len(cohort))
            self._collectors[task_id] = _RoundCollector(rec.round_idx,
                                                        cohort)
        return rec.round_idx, cohort

    def _void_round(self, rec: TaskRecord, coll: _RoundCollector,
                    reason: str = "all_dropped"):
        """Close the round WITHOUT aggregating: either nobody survived, or
        secure aggregation REFUSED the survivor set (every virtual group
        fell below ``min_survivors_per_vg`` — releasing such an aggregate
        would hand bare updates to the aggregator). The round index is not
        consumed; the next ``begin_round`` re-selects."""
        self._collectors.pop(rec.task_id, None)
        self.meters.counter("rounds_voided", task=rec.task_id).inc()
        if self.flight is not None:
            self.flight.record(rec.task_id, tracing.round_event(
                round_idx=rec.round_idx, cohort=list(coll.cohort),
                survivors=sorted(coll.results), voided=True,
                void_reason=reason))
        self.metrics.log(rec.task_id, rec.round_idx, round_voided=1,
                         n_selected=len(coll.cohort),
                         n_survived=len(coll.results),
                         n_dropped=len(coll.dropped))

    def _run_sync_aggregation(self, rec: TaskRecord, coll: _RoundCollector):
        strategy = self._strategies[rec.task_id]
        state = self._strategy_state[rec.task_id]
        try:
            with tracing.span("aggregate", task=rec.task_id,
                              round=coll.round_idx) as agg_sp:
                rec.model, state, info = run_sync_round(
                    rec.model, strategy, state, coll.results,
                    round_idx=coll.round_idx, vg_size=rec.config.vg_size,
                    secure_cfg=rec.config.secure_agg,
                    dp_cfg=rec.config.dp,
                    cohort=list(coll.cohort) if coll.dropped else None,
                    compressor=self._compressors.get(rec.task_id))
        except AggregationRefused:
            self._void_round(rec, coll, reason="aggregation_refused")
            return
        self._strategy_state[rec.task_id] = state
        # the round is closed — drop the collector so a straggling retry
        # (a late duplicate submit after a dropout report completed the
        # round) cannot re-trigger the aggregation
        self._collectors.pop(rec.task_id, None)
        rec.round_idx += 1
        self._record_flight(rec, coll, info, agg_sp,
                            survivors=sorted(coll.results))
        self._finish_round(rec, dict(info.metrics, n=info.n_participants,
                                     n_groups=info.n_groups,
                                     n_shards=info.n_shards,
                                     stage2_route=info.stage2_route,
                                     **_churn_metrics(info)))

    def _record_flight(self, rec: TaskRecord, coll: _RoundCollector,
                       info, span_tree, *, survivors):
        """Append the closed round's transcript event (cohort, survivors,
        stage timings from the aggregate span subtree, stage2 route) to
        the task's flight-recorder JSONL, when a recorder is installed."""
        if self.flight is None:
            return
        self.flight.record(rec.task_id, tracing.round_event(
            round_idx=info.round_idx, cohort=list(coll.cohort),
            survivors=list(survivors), n_shards=info.n_shards,
            stage2_route=info.stage2_route, span_tree=span_tree,
            metrics=_churn_metrics(info)))

    def _finish_round(self, rec: TaskRecord, metrics: dict):
        with tracing.span("finish_round", task=rec.task_id,
                          round=rec.round_idx):
            rec.history.append({"round": rec.round_idx, **metrics})
            self.metrics.log(rec.task_id, rec.round_idx, **metrics)
            tid = rec.task_id
            self.meters.counter("rounds_completed", task=tid).inc()
            # shape-contract probe: new compiled executables since the
            # last finished round across the shared jitted entry points
            cur = tracing.jit_cache_total()
            self.meters.counter("jit_cache_misses").inc(
                max(0, cur - self._jit_snapshot))
            self._jit_snapshot = cur
            if "upload_bytes_per_client" in metrics:
                self.meters.histogram("upload_bytes_per_client",
                                      task=tid) \
                    .observe(metrics["upload_bytes_per_client"])
            if "recovery_s" in metrics:
                self.meters.histogram("recovery_s", task=tid) \
                    .observe(metrics["recovery_s"])
            if self.flight is not None and rec.config.mode == "async":
                # async rounds close inside _finish_round (no collector /
                # aggregate span to lift a transcript from)
                self.flight.record(tid, {
                    "event": "server_step", "round": rec.round_idx,
                    "metrics": {k: v for k, v in metrics.items()
                                if isinstance(v, (int, float, str))}})
            acc = self._accountants.get(rec.task_id)
            if acc is not None:
                with tracing.span("accountant", task=tid):
                    self._step_accountant(rec, acc, metrics)
            self.check_stop(rec.task_id)

    def _step_accountant(self, rec: TaskRecord, acc, metrics: dict):
        pool = max(1, len(self.selection.registered(rec)))
        # mode-correct sample rate: an async server step composes over the
        # buffer_size clients that filled the FedBuff buffer; a sync round
        # over the clients whose data actually entered the aggregate — the
        # REALIZED participation ("n" = survivors), not clients_per_round,
        # which over-provisioned cohorts exceed (using the config target
        # would under-report epsilon)
        per_step = (rec.config.buffer_size if rec.config.mode == "async"
                    else metrics.get("n", rec.config.clients_per_round))
        acc.q = min(1.0, per_step / pool)
        acc.step()
        eps = self.epsilon(rec.task_id)
        if eps is not None:
            self.meters.gauge("epsilon_spent", task=rec.task_id).set(eps)

    def check_stop(self, task_id: int):
        """Evaluate the task's stop criteria; on the first one met,
        COMPLETE the task, record the reason and publish the final model
        (+ config, history, realized epsilon) to the model registry.
        Returns the stop reason, or None while still running. Called after
        every round; simulators may also call it after logging eval
        metrics (a ``target_metric`` may be an eval-time series)."""
        rec = self._tasks[task_id]
        if rec.status is TaskStatus.COMPLETED:
            return rec.stop_reason
        if rec.status is not TaskStatus.RUNNING:
            return None
        cfg = rec.config
        reason = None
        if rec.round_idx >= cfg.n_rounds:
            reason = "n_rounds"
        if reason is None and cfg.epsilon_budget is not None:
            eps = self.epsilon(task_id)
            if eps is not None and eps >= cfg.epsilon_budget:
                reason = "epsilon_budget"
        if reason is None and cfg.target_metric is not None \
                and cfg.target_value is not None:
            v = self.metrics.latest(task_id, cfg.target_metric)
            if v is not None and (v >= cfg.target_value
                                  if cfg.target_mode == "max"
                                  else v <= cfg.target_value):
                reason = "target_metric"
        if reason is None:
            return None
        rec.status = TaskStatus.COMPLETED
        rec.stop_reason = reason
        # free any leftover leases/round state: a completed task must not
        # pin devices other tasks could use
        self._abort_round(task_id)
        self.registry.publish(rec, epsilon=self.epsilon(task_id))
        return reason
