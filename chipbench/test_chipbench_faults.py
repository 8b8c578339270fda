"""The harness, with the timed path broken underneath, reads ``correct``
false: once for each fault a one-chip training cell can have.

- ``unchanged``: the server step returns its state unchanged;
- ``half_batch``: each client trains on half of its batch, the mean taken
  over the rest;
- ``loss_altered``: one client's loss is doubled where it is produced
  (a sum over a batch of two taken for its mean).

The look for a chip is skipped (``run_cell`` is called directly), and the
cells run at toy widths on the CPU.
"""
import numpy as np
import pytest

from chipbench import cells, run
from chipbench.conftest import CPU, MIXES, SEED


def _break(monkeypatch, fault):
    from repro.core import strategies

    if fault == "unchanged":
        monkeypatch.setattr(strategies.FedAvg, "apply",
                            lambda self, params, state, delta: (params, state))
        return
    build = cells.build

    def broken_build(*a, **k):
        cell = build(*a, **k)
        engine = cell.engine
        if fault == "half_batch":
            batch_fn = engine.batch_fn
            engine.batch_fn = lambda cid, r: {
                key: v[:, :v.shape[1] // 2]
                for key, v in batch_fn(cid, r).items()}
        else:
            stacked = engine.run_cohort_stacked

            def altered(*args):
                deltas, losses, n = stacked(*args)
                losses = np.array(losses)
                losses[0] *= 2.0
                return deltas, losses, n
            engine.run_cohort_stacked = altered
        return cell
    monkeypatch.setattr(cells, "build", broken_build)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "loss_altered"])
@pytest.mark.parametrize("name", MIXES)
def test_broken_path_is_not_correct(toy, monkeypatch, name, fault):
    config, workload = toy(name)
    _break(monkeypatch, fault)
    res = run.run_cell(name, SEED, 0.2, False, run.load_benchmark(),
                       dict(CPU), config=config, workload=workload)
    assert res["correct"] is False, res["checks"]
