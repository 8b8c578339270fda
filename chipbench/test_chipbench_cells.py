"""Each cell drives one tiny run on the CPU, through the harness's own
``run_cell``, at toy widths; and the command refuses to run without a
TPU."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import run
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


def test_no_tpu_refuses_before_any_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         MIXES[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
