"""Text renderings of the Florida dashboard views (paper Figs. 6-9):
task management list, task view with round history, and metric plots
(unicode sparkline charts standing in for the web UI)."""
from __future__ import annotations

BLOCKS = " ▁▂▃▄▅▆▇█"


def sparkline(values, width=48):
    if not values:
        return "(no data)"
    vals = values[-width:]
    lo, hi = min(vals), max(vals)
    rng = (hi - lo) or 1.0
    return "".join(BLOCKS[1 + int((v - lo) / rng * (len(BLOCKS) - 2))]
                   for v in vals)


def render_task_list(svc) -> str:
    # round column is "rrr/nnn": 3 digits a side keeps the columns
    # aligned past round 99 (the old 2-digit field drifted)
    rows = [f"{'id':>4} {'task':<18} {'status':<10} {'mode':<6} "
            f"{'round':>7} {'clients':>7}"]
    rows.append("-" * len(rows[0]))
    for t in svc.list_tasks():
        registered = len(svc.selection.registered(t))
        rows.append(f"{t.task_id:>4} {t.config.task_name:<18} "
                    f"{t.status.value:<10} {t.config.mode:<6} "
                    f"{t.round_idx:>3}/{t.config.n_rounds:<3} "
                    f"{registered:>7}")
    return "\n".join(rows)


def render_task_view(svc, task_id: int) -> str:
    t = svc.get_task(task_id)
    c = t.config
    lines = [
        f"Task {t.task_id}: {c.task_name}   [{t.status.value}]",
        f"  app={c.app_name} workflow={c.workflow_name} mode={c.mode}",
        f"  rounds: {t.round_idx}/{c.n_rounds}   "
        f"clients/round: {c.clients_per_round}   vg_size: {c.vg_size}",
        f"  strategy: {c.strategy}   dp: {c.dp.mechanism}"
        + (f" (clip={c.dp.clip_norm}, z={c.dp.noise_multiplier})"
           if c.dp.mechanism != "off" else ""),
    ]
    eps = svc.epsilon(task_id)
    if eps is not None:
        lines.append(f"  privacy spent: epsilon={eps:.2f} "
                     f"at delta={c.dp.delta}")
    churn = svc.metrics.churn_summary(task_id)
    if churn["dropped"] or churn["rounds_voided"] \
            or c.overprovision > 1.0:
        lines.append(
            f"  churn: selected={churn['selected']} "
            f"survived={churn['survived']} dropped={churn['dropped']} "
            f"({churn['dropout_rate']:.1%}) "
            f"recovery={churn['recovery_s'] * 1e3:.1f}ms"
            + (f" voided_rounds={churn['rounds_voided']}"
               if churn["rounds_voided"] else ""))
    if t.history:
        lines.append("  round history:")
        for h in t.history[-8:]:
            extras = " ".join(f"{k}={v:.4g}" for k, v in h.items()
                              if k != "round" and isinstance(v, float))
            lines.append(f"    round {h['round']:>3}: {extras}")
    return "\n".join(lines)


def render_fleet(plane) -> str:
    """Control-plane view: the shared fleet, per-task scheduling shares
    (priority / weight / lease-seconds), and the model registry."""
    d = plane.directory
    fleet = d.fleet_summary()
    fair = plane.fairness()
    lines = [
        f"fleet: {fleet['devices']} devices, "
        f"{fleet['leased_now']} leased now, "
        f"{fleet['tasks_enrolled']} tasks enrolled",
        f"{'id':>4} {'task':<18} {'status':<10} {'mode':<6} {'prio':>4} "
        f"{'weight':>6} {'lease_s':>9} {'rounds':>6}",
    ]
    lines.append("-" * len(lines[-1]))
    for t in plane.tasks():
        f = fair.get(t.task_id, {})
        lines.append(
            f"{t.task_id:>4} {t.config.task_name:<18} "
            f"{t.status.value:<10} {t.config.mode:<6} "
            f"{f.get('priority', 0):>4} {f.get('weight', 1.0):>6.2f} "
            f"{f.get('lease_seconds', 0.0):>9.2f} "
            f"{f.get('rounds_granted', 0):>6}")
    lines.append(f"registry: {len(plane.registry)} published model(s)"
                 + ("".join(f"\n  task {e.task_id} ({e.task_name}): "
                            f"{e.rounds_run} rounds, stop={e.stop_reason}"
                            + (f", eps={e.epsilon:.2f}"
                               if e.epsilon is not None else "")
                            for e in plane.registry.entries())))
    return "\n".join(lines)


def render_metrics(svc, task_id: int) -> str:
    """Fig. 8/9 analogue: per-metric sparkline series."""
    rows = [f"metrics for task {task_id}:"]
    metrics = sorted({r["metric"] for r in svc.metrics._rows[task_id]})
    if not metrics:
        return rows[0] + " (none)"
    for m in metrics:
        rounds, vals = svc.metrics.series(task_id, m)
        if not vals:
            continue   # non-numeric series (stage2_route etc.)
        rows.append(f"  {m:<18} {sparkline(vals)}  "
                    f"last={vals[-1]:.4g} (n={len(vals)})")
    return "\n".join(rows)


def render_status(svc) -> str:
    """``florida status``: the task list plus the service's typed meter
    registry (counters / gauges / histogram means)."""
    lines = [render_task_list(svc), "", "meters:"]
    snap = svc.meters.snapshot()
    if not snap:
        lines.append("  (none)")
    for row in snap:
        labels = ",".join(f"{k}={v}" for k, v in
                          sorted(row["labels"].items()))
        name = row["name"] + (f"{{{labels}}}" if labels else "")
        if row["kind"] == "histogram":
            lines.append(f"  {name:<40} n={row['count']:<6} "
                         f"mean={row['mean']:.4g}")
        else:
            lines.append(f"  {name:<40} {row['value']:.6g}")
    return "\n".join(lines)


def render_trace(svc, task_id: int, last: int = 8) -> str:
    """``florida trace <task>``: the flight-recorder round transcript —
    per-round stage tree with wall-clock timings."""
    if svc.flight is None:
        return f"task {task_id}: no flight recorder installed"
    events = svc.flight.read(task_id)
    if not events:
        return f"task {task_id}: no flight records"
    lines = [f"flight transcript for task {task_id} "
             f"({len(events)} events, showing last {min(last, len(events))}):"]
    for ev in events[-last:]:
        head = f"round {ev.get('round'):>3} [{ev.get('event')}]"
        parts = [f"cohort={len(ev.get('cohort', []))}",
                 f"survivors={len(ev.get('survivors', []))}"]
        if ev.get("stage2_route"):
            parts.append(f"route={ev['stage2_route']}")
        if ev.get("n_shards"):
            parts.append(f"shards={ev['n_shards']}")
        if ev.get("void_reason"):
            parts.append(f"void={ev['void_reason']}")
        if ev.get("wall_ms") is not None:
            parts.append(f"wall={ev['wall_ms']:.1f}ms")
        lines.append(f"  {head}  " + " ".join(parts))
        for st in ev.get("stages", []):
            lines.append(f"    {'  ' * st['depth']}{st['name']:<20} "
                         f"{st['dur_ms']:>9.3f}ms")
    return "\n".join(lines)
