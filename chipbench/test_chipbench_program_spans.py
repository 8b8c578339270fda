"""The program's own spans on the device trace's clock (``progtrace``,
``trace_program``, ``run.trace_window``): the idle reduction on hand-made
summaries; a toy cell's rounds traced on the CPU with the program
mirroring its spans into the profiler; and a small trace recorded on a
TPU v5e with program spans (``testdata/program_v5e.*``, written by
``record_program_testdata.py``)."""
import json
import os

import jax
import numpy as np
import pytest

from chipbench import cells, devtrace, progtrace, run, trace_program
from chipbench.conftest import SEED, toy_cell
from repro import tracing

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
MS = 1_000_000


def summary():
    """The device is busy over 0-10, 30-40 and 60-100 ms, idle over 10-30
    and 40-60. Program spans: ``a`` over 5-25 holding ``b`` over 15-20,
    ``c`` over 45-50, and ``d`` over 70-90, where the device is busy."""
    return {
        "ops": [["op", 0, 10 * MS], ["op", 30 * MS, 10 * MS],
                ["op", 60 * MS, 40 * MS]],
        "program_spans": [["a", 5 * MS, 20 * MS], ["b", 15 * MS, 5 * MS],
                          ["c", 45 * MS, 5 * MS], ["d", 70 * MS, 20 * MS]],
    }


def test_idle_gap_is_split_at_span_edges():
    # the gap 10-30 is cut at a's end (25), not given whole by its middle
    assert progtrace.idle_in_spans(summary(), {"a"}, 0, 100 * MS) == 15 * MS
    assert progtrace.idle_in_spans(summary(), {"a"}, 0, 20 * MS) == 10 * MS


def test_nested_spans_count_once():
    s = summary()
    assert progtrace.idle_in_spans(s, {"b"}, 0, 100 * MS) == 5 * MS
    assert progtrace.idle_in_spans(s, {"a", "b"}, 0, 100 * MS) == 15 * MS


def test_idle_outside_program_spans_is_not_counted():
    s = summary()
    every = {"a", "b", "c", "d"}
    assert progtrace.length(progtrace.idle(s, 0, 100 * MS)) == 40 * MS
    assert progtrace.idle_in_spans(s, every, 0, 100 * MS) == 20 * MS
    assert progtrace.idle_in_spans(s, {"d"}, 0, 100 * MS) == 0
    assert dict(progtrace.idle_by_span(s, 0, 100 * MS)) == {
        "(none)": 20 * MS, "a": 10 * MS, "b": 5 * MS, "c": 5 * MS}


def test_metrics_read_nothing_from_a_program_that_mirrors_nothing():
    ctx = {"rounds": 2, "program_idle_ns": lambda names: 0,
           "transfer_bytes": 0}
    for name in trace_program.METRICS:
        assert run.load_reader(name)(ctx) == 0.0
        assert run.load_reader(name)({"rounds": 2}) is None


# the benchmark span that calls each program span, by cell
CALLER = {
    "begin_round": "begin_round", "selection": "begin_round",
    "snapshot": "model_snapshot", "deserialize": "deserialize",
    "local_train": "local_train", "make_batches": "local_train",
    "train_wave": "local_train", "wave_to_host": "local_train",
    "stack_waves": "local_train",
    "report_dropout": "submit_cohort", "vg_plan": "submit_cohort",
    "secure_agg": "submit_cohort", "ravel_rows": "submit_cohort",
    "scatter_survivors": "submit_cohort",
    "cohort_interims": "submit_cohort", "mask_recovery": "submit_cohort",
    "limb_combine": "submit_cohort", "server_update": "submit_cohort",
    "finish_round": "submit_cohort", "accountant": "submit_cohort",
}
WAVES_ONLY = {"train_wave", "wave_to_host", "stack_waves"}
CHURN_ONLY = {"report_dropout", "scatter_survivors", "mask_recovery"}
ROUNDS = 2


@pytest.fixture(scope="module", params=["spam-paper",
                                        "spam-cohort128-churn"])
def traced(request, tmp_path_factory):
    name = request.param
    config, workload = toy_cell(name)
    cell = cells.build(name, SEED, config=config, workload=workload)
    cell.run_round()                                   # compiles
    summary, tracer, _, _ = run.trace_window(
        cell, float("inf"), ROUNDS, str(tmp_path_factory.mktemp("trace")))
    return name, cell, summary, tracer


def test_program_spans_nest_in_the_benchmark_calls(traced):
    name, _, summary, _ = traced
    want = set(CALLER)
    if name == "spam-paper":
        want -= WAVES_ONLY | CHURN_ONLY
    seen = {n for n, _, _ in summary["program_spans"]}
    assert want <= seen
    bench = [(n[len(devtrace.SPAN_PREFIX):], s, s + d)
             for n, s, d in summary["spans"]]
    for n, s, d in summary["program_spans"]:
        if n in CALLER:
            assert any(b == CALLER[n] and bs <= s and s + d <= be
                       for b, bs, be in bench), n
    # one round of the benchmark's holds each round's begin_round
    assert sum(n == "begin_round" for n, _, _ in
               summary["program_spans"]) == ROUNDS


def _nbytes(tree) -> int:
    return sum(int(np.asarray(x).nbytes) for x in jax.tree.leaves(tree))


def test_bytes_are_the_bytes_the_shapes_give(traced):
    name, cell, _, tracer = traced
    totals = trace_program.span_totals(tracer)
    task = cell.workload["task"]
    n = task["clients_per_round"] - task.get("drop_per_round", 0)
    model = _nbytes(cell.model_now())
    batch = _nbytes(cell.traffic.batch_fn("client-0000", 0))
    per_round = {k: {"h2d_bytes": t["h2d_bytes"] // ROUNDS,
                     "d2h_bytes": t["d2h_bytes"] // ROUNDS}
                 for k, t in totals.items()}
    assert per_round["snapshot"] == {"h2d_bytes": 0, "d2h_bytes": model}
    w = task["wave_size"]
    if not w:
        # host params and the cohort's batches go up once; the deltas
        # stay on the device
        assert per_round["make_batches"]["h2d_bytes"] == model + n * batch
        assert per_round["ravel_rows"]["h2d_bytes"] == 0
        want = 2 * model + n * batch
    else:
        waves = -(-n // w)
        assert per_round["make_batches"]["h2d_bytes"] == \
            waves * (model + w * batch)
        # each wave comes back whole (pad rows too), deltas and losses
        assert per_round["wave_to_host"]["d2h_bytes"] == \
            waves * w * (model + 4)
        assert per_round["stack_waves"]["h2d_bytes"] == 4 * n
        # the survivors' rows go back up to be raveled
        assert per_round["ravel_rows"]["h2d_bytes"] == n * model
        want = model + waves * (model + w * batch) \
            + waves * w * (model + 4) + 4 * n + n * model
    assert trace_program.transfer_bytes(tracer) == ROUNDS * want


def test_null_tracer_counts_no_bytes(traced, monkeypatch):
    _, cell, _, _ = traced

    def counted(*a, **k):
        raise AssertionError("bytes counted with tracing off")

    monkeypatch.setattr(tracing, "tree_bytes", counted)
    assert not tracing.enabled()
    cell.run_round()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTDATA, "program_v5e.json")) as f:
        meta = json.load(f)
    return meta, devtrace.load(os.path.join(TESTDATA,
                                            "program_v5e.xplane.pb.gz"))


def test_recorded_trace_holds_program_spans_in_benchmark_spans(recorded):
    meta, s = recorded
    names = sorted(n for n, _, _ in s["program_spans"])
    assert names == sorted(["local_train", "snapshot", "secure_agg"]
                           * meta["rounds"])
    assert s["ops"] and s["spans"]


def test_recorded_trace_metrics_read_the_host_sleeps(recorded):
    meta, s = recorded
    rounds = meta["rounds"]
    lo, hi = devtrace.window(s)
    ctx = {"rounds": rounds, "transfer_bytes": meta["transfer_bytes"],
           "program_idle_ns": lambda names: progtrace.idle_in_spans(
               s, names, lo, hi)}
    sleep_ms = {k: v * 1e3 for k, v in meta["sleep_s"].items()}
    for metric, span in [("train_idle_ms", "local_train"),
                         ("service_idle_ms", "snapshot"),
                         ("chain_idle_ms", "secure_agg")]:
        v = run.load_reader(metric)(ctx)
        assert sleep_ms[span] <= v < 1.5 * sleep_ms[span] + 2, metric
    assert run.load_reader("host_transfer_mb")(ctx) == \
        meta["transfer_bytes"] / 1e6 / rounds
    # the sleep outside every program span is idle no metric reads
    outside = dict(progtrace.idle_by_span(s, lo, hi))["(none)"] / 1e6
    assert outside >= rounds * sleep_ms["outside"]
