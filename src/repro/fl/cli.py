"""Florida CLI (paper §3.3: "a command-line interface for scripting service
and workflow management" with the same functionality as the web UI).

Because the container runs everything in-process, the CLI operates on a
*service session file*: commands construct/load a ManagementService whose
task state persists between invocations via the checkpoint module.

    PYTHONPATH=src python -m repro.fl.cli create --task-name spam \\
        --app-name spam-app --workflow train --clients-per-round 8 \\
        --rounds 5 [--dp local --noise 1.0 --clip 0.5] [--mode async]
    PYTHONPATH=src python -m repro.fl.cli list
    PYTHONPATH=src python -m repro.fl.cli deploy <task_id>
    PYTHONPATH=src python -m repro.fl.cli run <task_id> [...] --clients 16
    PYTHONPATH=src python -m repro.fl.cli show <task_id>
    PYTHONPATH=src python -m repro.fl.cli pause|resume|cancel <task_id>
    PYTHONPATH=src python -m repro.fl.cli metrics <task_id>
    PYTHONPATH=src python -m repro.fl.cli fleet
    PYTHONPATH=src python -m repro.fl.cli registry [--save-dir DIR]

``run`` with several task ids drives them CONCURRENTLY through the
:class:`~repro.fl.scheduler.ControlPlane` over one shared client
population (the multi-tenant path); with one id it uses the direct
single-task simulators, which the scheduler path reproduces bit-for-bit.
"""
from __future__ import annotations

import argparse
import os
import pickle
import sys

from repro.core.dp import DPConfig
from repro import tracing
from repro.fl.dashboard import (render_fleet, render_metrics,
                                render_status, render_task_list,
                                render_task_view, render_trace)
from repro.fl.scheduler import ControlPlane
from repro.fl.server import ManagementService
from repro.fl.task import CompressionConfig, TaskConfig

DEFAULT_SESSION = os.environ.get("FLORIDA_SESSION",
                                 os.path.expanduser("~/.florida-session.pkl"))


def load_service(path=DEFAULT_SESSION) -> ManagementService:
    if os.path.exists(path):
        with open(path, "rb") as f:
            svc = pickle.load(f)
        # sessions saved before the observability layer grew these
        if not hasattr(svc, "meters"):
            from repro.fl.telemetry import MetricsRegistry
            svc.meters = MetricsRegistry()
        if not hasattr(svc, "flight"):
            svc.flight = None
        if not hasattr(svc, "_jit_snapshot"):
            svc._jit_snapshot = tracing.jit_cache_total()
        return svc
    return ManagementService()


def save_service(svc, path=DEFAULT_SESSION):
    with open(path, "wb") as f:
        pickle.dump(svc, f)


def cmd_create(svc, args):
    import jax
    from repro.configs import get_config
    from repro.models import classifier_init, init_params
    cfg = get_config("bert-tiny-spam").replace(vocab_size=1024, d_model=64,
                                               d_ff=128)
    key = jax.random.PRNGKey(args.seed)
    model = {"trunk": init_params(cfg, key),
             "head": classifier_init(cfg, jax.random.fold_in(key, 1))}
    dp = DPConfig(mechanism=args.dp, clip_norm=args.clip,
                  noise_multiplier=args.noise) if args.dp != "off" \
        else DPConfig()
    comp = CompressionConfig(kind="topk", frac=args.topk_frac,
                             error_feedback=not args.no_error_feedback) \
        if args.topk_frac > 0 else CompressionConfig()
    tc = TaskConfig(task_name=args.task_name, app_name=args.app_name,
                    workflow_name=args.workflow,
                    clients_per_round=args.clients_per_round,
                    n_rounds=args.rounds, strategy=args.strategy,
                    mode=args.mode, vg_size=args.vg_size, dp=dp,
                    compression=comp,
                    priority=args.priority, weight=args.weight,
                    epsilon_budget=args.epsilon_budget,
                    target_metric=args.target_metric,
                    target_value=args.target_value)
    tid = svc.create_task(tc, model, user=args.user,
                          deploy=not args.no_deploy)
    state = "created" if args.no_deploy else "created + deployed"
    print(f"{state} task {tid} ({args.task_name})")
    return tid


def _spam_world(model0=None):
    sys.path.insert(0, os.getcwd())
    from benchmarks.common import SpamWorld
    world = SpamWorld(vocab=1024, d_model=64, n_train=3000, n_splits=20,
                      frac=0.5)
    if model0 is not None:
        world.model0 = model0  # continue from the task's current snapshot
    return world


def cmd_run(svc, args):
    """Drive task(s) with simulated SDK clients (the CLI's test harness).
    One task id -> the direct single-task simulators; several -> the
    ControlPlane-scheduled multi-task simulator over one shared fleet.

    Tracing is ON by default for CLI runs (``--no-trace`` opts out): a
    collecting tracer records the full round span tree, the service gets
    a flight recorder next to the session file, and the run's Perfetto
    timeline is exported to ``<session>.flight/perfetto_run.json``."""
    if args.no_trace:
        _run_tasks(svc, args)
        return
    svc.flight = tracing.FlightRecorder(args.session + ".flight")
    with tracing.use_tracer(tracing.Tracer()) as tracer:
        try:
            _run_tasks(svc, args)
        finally:
            out = os.path.join(svc.flight.root, "perfetto_run.json")
            tracer.export_perfetto(out)
            print(f"trace: {tracer.n_spans} spans -> {out}")


def _run_tasks(svc, args):
    from repro.fl.simulator import (make_heterogeneous_clients,
                                    run_async_simulation,
                                    run_multi_task_simulation,
                                    run_sync_simulation)
    if len(args.task_id) == 1:
        task = svc.get_task(args.task_id[0])
        world = _spam_world(task.model)
        clients = make_heterogeneous_clients(args.clients, world.make_trainer)
        runner = (run_async_simulation if task.config.mode == "async"
                  else run_sync_simulation)
        res = runner(svc, args.task_id[0], clients,
                     eval_fn=world.test_accuracy)
        accs = [h.get("eval_accuracy") for h in res.metrics_history]
        print(f"task {args.task_id[0]}: {len(res.round_durations)} "
              f"iterations, acc {accs[0]:.3f} -> {accs[-1]:.3f}"
              if accs else "no rounds ran")
        return
    world = _spam_world()
    clients = make_heterogeneous_clients(args.clients, world.make_trainer)
    plane = ControlPlane(svc)
    for tid in args.task_id:
        if svc.get_task(tid).status.value == "created":
            plane.deploy(tid, user=args.user)
    res = run_multi_task_simulation(
        plane, clients,
        eval_fns={tid: world.test_accuracy for tid in args.task_id})
    for tid in args.task_id:
        r = res.per_task[tid]
        rec = svc.get_task(tid)
        print(f"task {tid}: {len(r.round_durations)} iterations, "
              f"status={rec.status.value}"
              + (f" (stop: {rec.stop_reason})" if rec.stop_reason else ""))
    if res.lease_overlaps:
        print(f"WARNING: {len(res.lease_overlaps)} overlapping sync leases")
    print(render_fleet(plane))


def cmd_registry(svc, args):
    reg = svc.registry
    if not len(reg):
        print("registry: no published models")
        return
    for e in reg.entries():
        eps = f" eps={e.epsilon:.2f}" if e.epsilon is not None else ""
        print(f"task {e.task_id} ({e.task_name}): {e.rounds_run} rounds, "
              f"stop={e.stop_reason}{eps}, "
              f"published_at={e.published_at:.1f}")
    if args.save_dir:
        reg.save(args.save_dir)
        print(f"saved {len(reg)} model(s) to {args.save_dir}")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="florida")
    ap.add_argument("--session", default=DEFAULT_SESSION)
    ap.add_argument("--user", default="default-user")
    sub = ap.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("create")
    c.add_argument("--task-name", required=True)
    c.add_argument("--app-name", required=True)
    c.add_argument("--workflow", required=True)
    c.add_argument("--clients-per-round", type=int, default=8)
    c.add_argument("--rounds", type=int, default=5)
    c.add_argument("--strategy", default="fedavg",
                   choices=["fedavg", "fedavgm", "fedprox", "dga"])
    c.add_argument("--mode", default="sync", choices=["sync", "async"])
    c.add_argument("--vg-size", type=int, default=4)
    c.add_argument("--dp", default="off", choices=["off", "local", "global"])
    c.add_argument("--clip", type=float, default=0.5)
    c.add_argument("--noise", type=float, default=1.0)
    c.add_argument("--topk-frac", type=float, default=0.0,
                   help="top-k update compression: transmit this fraction "
                        "of the flat update per round (0 = dense)")
    c.add_argument("--no-error-feedback", action="store_true",
                   help="disable the per-client residual carry (plain "
                        "rand-k; diagnostics only)")
    c.add_argument("--seed", type=int, default=0)
    c.add_argument("--no-deploy", action="store_true",
                   help="leave the task CREATED (deploy it later)")
    c.add_argument("--priority", type=int, default=0)
    c.add_argument("--weight", type=float, default=1.0)
    c.add_argument("--epsilon-budget", type=float, default=None)
    c.add_argument("--target-metric", default=None)
    c.add_argument("--target-value", type=float, default=None)

    sub.add_parser("list")
    sub.add_parser("fleet")
    for name in ("show", "deploy", "pause", "resume", "cancel", "metrics"):
        p = sub.add_parser(name)
        p.add_argument("task_id", type=int)
    r = sub.add_parser("run")
    r.add_argument("task_id", type=int, nargs="+")
    r.add_argument("--clients", type=int, default=16)
    r.add_argument("--no-trace", action="store_true",
                   help="disable the flight recorder + Perfetto export "
                        "for this run")
    g = sub.add_parser("registry")
    g.add_argument("--save-dir", default=None)
    sub.add_parser("status")
    t = sub.add_parser("trace")
    t.add_argument("task_id", type=int)
    t.add_argument("--perfetto", default=None, metavar="OUT",
                   help="also rebuild a Perfetto trace_events JSON from "
                        "the task's flight records and write it here")

    args = ap.parse_args(argv)
    svc = load_service(args.session)
    if args.cmd == "create":
        cmd_create(svc, args)
    elif args.cmd == "list":
        print(render_task_list(svc))
    elif args.cmd == "fleet":
        print(render_fleet(ControlPlane(svc)))
    elif args.cmd == "deploy":
        svc.deploy_task(args.task_id, user=args.user)
        print(f"task {args.task_id} deployed")
    elif args.cmd == "registry":
        cmd_registry(svc, args)
    elif args.cmd == "show":
        print(render_task_view(svc, args.task_id))
    elif args.cmd == "metrics":
        print(render_metrics(svc, args.task_id))
    elif args.cmd == "pause":
        svc.pause_task(args.task_id, user=args.user)
        print(f"task {args.task_id} paused")
    elif args.cmd == "resume":
        svc.resume_task(args.task_id, user=args.user)
        print(f"task {args.task_id} resumed")
    elif args.cmd == "cancel":
        svc.cancel_task(args.task_id, user=args.user)
        print(f"task {args.task_id} cancelled")
    elif args.cmd == "run":
        cmd_run(svc, args)
    elif args.cmd == "status":
        print(render_status(svc))
    elif args.cmd == "trace":
        print(render_trace(svc, args.task_id))
        if args.perfetto:
            if svc.flight is None:
                print("no flight recorder: nothing to export")
            else:
                import json
                events = svc.flight.read(args.task_id)
                with open(args.perfetto, "w") as f:
                    json.dump(tracing.perfetto_from_flight(
                        events, args.task_id), f)
                print(f"wrote {args.perfetto} ({len(events)} rounds)")
    save_service(svc, args.session)


if __name__ == "__main__":
    # the command-line entry point; in-process callers of main() (tests,
    # notebooks) keep their own JAX cache settings
    from repro import compile_cache
    compile_cache.configure()
    main()
