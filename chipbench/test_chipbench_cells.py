"""Each cell drives one tiny run on the CPU, through the harness's own
``run_cell``, at toy widths; a traced run hands the program's spans and
counters to the per-layer readers; and the command refuses to run without
a TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import cells, run
from chipbench.conftest import CPU, MIXES, SEED


@pytest.mark.parametrize("name", MIXES)
def test_tiny_run_is_correct(toy, name):
    bench = run.load_benchmark()
    config, workload = toy(name)
    res = run.run_cell(name, SEED, 0.3, False, bench, dict(CPU),
                       config=config, workload=workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in run.metrics_for(bench["end_to_end"], name)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    json.dumps(res)


# the per-layer metrics that read the program's spans and counters; the
# others need a TPU's trace or its table of peaks
PROGRAM_SPAN_METRICS = ("service_idle_ms", "train_idle_ms", "chain_idle_ms",
                        "host_transfer_mb")


def _nbytes(tree) -> int:
    return sum(int(np.asarray(x).nbytes) for x in jax.tree.leaves(tree))


def test_traced_run_reads_the_program_spans(toy, monkeypatch):
    name = "spam-paper"
    bench = run.load_benchmark()
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in PROGRAM_SPAN_METRICS]
    seen = []
    load_reader = run.load_reader

    def spied(metric):
        read = load_reader(metric)
        return lambda ctx: seen.append(ctx) or read(ctx)
    monkeypatch.setattr(run, "load_reader", spied)
    config, workload = toy(name)
    res = run.run_cell(name, SEED, 0.3, True, bench, dict(CPU),
                       config=config, workload=workload)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == set(PROGRAM_SPAN_METRICS)
    ctx, rounds = seen[0], res["attempted"]
    assert ctx["rounds"] == rounds
    # no device ops on the CPU: the whole window is idle, inside spans too
    assert ctx["program_idle_ns"](("local_train",)) > 0
    assert ctx["program_counts"]("no_such_counter") is None
    # the bytes the shapes give: the model pulled for the snapshot, then
    # the host params and the cohort's batches sent up; no waves
    cell = cells.build(name, SEED, config=config, workload=workload)
    model = _nbytes(cell.template)
    batch = _nbytes(cell.traffic.batch_fn("client-0000", 0))
    n = workload["task"]["clients_per_round"]
    assert ctx["program_counts"]("d2h_bytes") == rounds * model
    assert ctx["program_counts"]("h2d_bytes") == rounds * (model + n * batch)
    want = rounds * (2 * model + n * batch)
    assert ctx["transfer_bytes"] == want
    assert res["metrics"]["host_transfer_mb"]["value"] == want / 1e6 / rounds


def test_no_tpu_refuses_before_any_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         MIXES[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
