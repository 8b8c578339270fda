"""CLI + dashboard rendering (paper §3.3)."""
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.fl import ManagementService, TaskConfig
from repro.fl.dashboard import (render_metrics, render_status,
                                render_task_list, render_task_view,
                                render_trace, sparkline)


def _svc_with_task(**kw):
    svc = ManagementService()
    tid = svc.create_task(
        TaskConfig("spam-demo", "app", "wf", clients_per_round=2,
                   n_rounds=3, vg_size=2, **kw),
        {"w": jnp.zeros(4)})
    return svc, tid


def test_sparkline():
    assert sparkline([]) == "(no data)"
    s = sparkline([0, 1, 2, 3])
    assert len(s) == 4 and s[0] != s[-1]


def test_task_list_and_view():
    svc, tid = _svc_with_task()
    out = render_task_list(svc)
    assert "spam-demo" in out and "running" in out
    view = render_task_view(svc, tid)
    assert "rounds: 0/3" in view and "fedavg" in view


def test_metrics_render():
    svc, tid = _svc_with_task()
    svc.metrics.log(tid, 1, accuracy=0.5)
    svc.metrics.log(tid, 2, accuracy=0.8)
    out = render_metrics(svc, tid)
    assert "accuracy" in out and "last=0.8" in out


def test_cli_session_round_trip(tmp_path):
    from repro.fl import cli
    session = str(tmp_path / "s.pkl")
    cli.main(["--session", session, "create", "--task-name", "t1",
              "--app-name", "a", "--workflow", "w",
              "--clients-per-round", "2", "--rounds", "2"])
    svc = cli.load_service(session)
    tasks = svc.list_tasks()
    assert len(tasks) == 1 and tasks[0].config.task_name == "t1"
    cli.main(["--session", session, "pause", str(tasks[0].task_id)])
    svc = cli.load_service(session)
    assert svc.list_tasks()[0].status.value == "paused"
    cli.main(["--session", session, "list"])
    cli.main(["--session", session, "show", str(tasks[0].task_id)])


def test_cli_session_reload_never_reuses_task_ids(tmp_path):
    """Regression: task ids must be derived from the service's task
    store, not a process-global counter — a create in a FRESH process
    against a reloaded session used to collide with (and clobber) the
    task created before the save."""
    from repro.fl import cli
    session = str(tmp_path / "s.pkl")
    cli.main(["--session", session, "create", "--task-name", "first",
              "--app-name", "a", "--workflow", "w",
              "--clients-per-round", "2", "--rounds", "2"])
    # a fresh python process has a fresh module counter; simulate it by
    # resetting the fallback counter before the reloaded create
    import repro.fl.task as task_mod
    task_mod._task_counter = 0
    cli.main(["--session", session, "create", "--task-name", "second",
              "--app-name", "a", "--workflow", "w",
              "--clients-per-round", "2", "--rounds", "2"])
    svc = cli.load_service(session)
    tasks = svc.list_tasks()
    assert len(tasks) == 2
    names = {t.config.task_name for t in tasks}
    assert names == {"first", "second"}
    ids = [t.task_id for t in tasks]
    assert len(set(ids)) == 2


def test_cli_deploy_and_registry(tmp_path, capsys):
    from repro.fl import cli
    session = str(tmp_path / "s.pkl")
    cli.main(["--session", session, "create", "--task-name", "t1",
              "--app-name", "a", "--workflow", "w",
              "--clients-per-round", "2", "--rounds", "2", "--no-deploy"])
    svc = cli.load_service(session)
    assert svc.list_tasks()[0].status.value == "created"
    tid = svc.list_tasks()[0].task_id
    cli.main(["--session", session, "deploy", str(tid)])
    svc = cli.load_service(session)
    assert svc.list_tasks()[0].status.value == "running"
    cli.main(["--session", session, "registry"])
    assert "no published models" in capsys.readouterr().out
    cli.main(["--session", session, "fleet"])
    assert "fleet:" in capsys.readouterr().out


def test_fleet_render():
    from repro.fl.dashboard import render_fleet
    from repro.fl.scheduler import ControlPlane
    svc, tid = _svc_with_task()
    out = render_fleet(ControlPlane(svc))
    assert "spam-demo" in out and "registry: 0" in out


# ---------------------------------------------------------------------------
# renderer edge cases (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_sparkline_constant_series():
    # all-equal values: the range fallback must not divide by zero, and
    # every point lands on the same block
    s = sparkline([2.0] * 10)
    assert len(s) == 10 and len(set(s)) == 1
    assert sparkline([0.0]) != "(no data)"


def test_sparkline_window_width():
    s = sparkline(list(range(200)), width=48)
    assert len(s) == 48
    assert s[-1] == sparkline([0, 1])[-1]   # max block at the tail


def test_task_list_alignment_past_round_99():
    svc = ManagementService()
    t1 = svc.create_task(
        TaskConfig("long-runner", "app", "wf", clients_per_round=2,
                   n_rounds=150, vg_size=2), {"w": jnp.zeros(4)})
    t2 = svc.create_task(
        TaskConfig("fresh", "app", "wf", clients_per_round=2,
                   n_rounds=3, vg_size=2), {"w": jnp.zeros(4)})
    svc.get_task(t1).round_idx = 120
    out = render_task_list(svc)
    assert "120/150" in out
    # 3-digit round fields keep every data row the same width — the old
    # 2-digit format drifted the columns once a task passed round 99
    lines = out.splitlines()
    assert len({len(ln) for ln in lines[2:]}) == 1


# ---------------------------------------------------------------------------
# scripted 2-task simulation driving every renderer (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def _trainer_factory(i):
    def trainer(blob, round_idx):
        return {"w": np.full(8, 0.01, np.float32)}, 10, {"loss": 1.0}
    return trainer


def _run_two_task_sim(tmp_path):
    from repro.fl import ControlPlane, run_multi_task_simulation
    from repro.fl.simulator import make_heterogeneous_clients
    plane = ControlPlane(seed=0)
    tids = [plane.create_task(
        TaskConfig(name, "app", "wf", clients_per_round=4, n_rounds=2,
                   vg_size=2), {"w": np.zeros(8, np.float32)})
        for name in ("alpha", "beta")]
    for t in tids:
        plane.deploy(t)
    svc = plane.service
    svc.flight = tracing.FlightRecorder(str(tmp_path / "flight"))
    with tracing.use_tracer(tracing.Tracer()) as tr:
        run_multi_task_simulation(
            plane, make_heterogeneous_clients(8, _trainer_factory),
            seed=0)
    return plane, svc, tids, tr


def test_two_task_sim_drives_all_renderers(tmp_path):
    from repro.fl.dashboard import render_fleet
    plane, svc, tids, tr = _run_two_task_sim(tmp_path)

    out = render_task_list(svc)
    assert "alpha" in out and "beta" in out and "completed" in out

    view = render_task_view(svc, tids[0])
    assert "rounds: 2/2" in view and "round history:" in view

    fleet = render_fleet(plane)
    assert "registry: 2 published model(s)" in fleet
    assert "8 devices" in fleet

    status = render_status(svc)
    assert "meters:" in status
    assert "rounds_completed{task=%d}" % tids[0] in status
    assert "rounds_granted" in status and "jit_cache_misses" in status
    assert "round_duration_s" in status and "lease_seconds" in status

    # scheduler-layer meters landed too (fair-share lease accounting)
    for tid in tids:
        assert svc.meters.value("rounds_granted", task=tid) == 2.0
        assert svc.meters.value("lease_seconds", task=tid) is not None

    # the grant decisions were traced alongside the round pipeline
    names = {s.name for r in tr.roots() for s in _span_tree(r)}
    assert {"grant_round", "lease_acquire", "local_train", "aggregate",
            "secure_agg", "server_update"} <= names


def _span_tree(span):
    out = [span]
    for c in span.children:
        out.extend(_span_tree(c))
    return out


def test_render_trace_transcript(tmp_path):
    _, svc, tids, _ = _run_two_task_sim(tmp_path)
    out = render_trace(svc, tids[1])
    assert f"flight transcript for task {tids[1]}" in out
    assert "round   0 [round]" in out and "round   1 [round]" in out
    assert "cohort=4 survivors=4" in out
    assert "route=single_dispatch" in out
    assert "aggregate" in out and "secure_agg" in out
    # the chain's stages run in one program: its host span, no copies of
    # its window under the fused stages' names
    assert "cohort_interims" in out and "(fused)" not in out
    # unknown task / missing recorder degrade to messages, not crashes
    assert "no flight records" in render_trace(svc, 999)
    svc.flight = None
    assert "no flight recorder" in render_trace(svc, tids[0])


def test_cli_status_and_trace_commands(tmp_path, capsys):
    from repro.fl import cli
    session = str(tmp_path / "s.pkl")
    cli.main(["--session", session, "create", "--task-name", "t1",
              "--app-name", "a", "--workflow", "w",
              "--clients-per-round", "2", "--rounds", "2"])
    capsys.readouterr()
    cli.main(["--session", session, "status"])
    out = capsys.readouterr().out
    assert "t1" in out and "meters:" in out
    cli.main(["--session", session, "trace", "1"])
    assert "no flight recorder" in capsys.readouterr().out
