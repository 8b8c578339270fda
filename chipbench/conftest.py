"""Toy widths for the benchmark's CPU tests. The cells run at published
widths only on the chip; here every width is cut so that a round takes
milliseconds, and the traffic keeps its shape (a client's batch draws
from several rows, so half of it differs from the whole). Each task kind
says how its cells shrink (``tasks/<kind>.py``, ``toy``)."""
import copy
import os

import pytest

from chipbench import cells, tasks

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 12345      # past 32 signed bits, as the driver's are
# every traffic mix the benchmark holds, listed in BENCHMARK.json or not
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(cells.HERE,
                                                       "workloads"))
               if f.endswith(".json"))


def toy_cell(name: str) -> tuple:
    """``(config, workload)`` of cell ``name``, shrunk by its task kind's
    ``toy``."""
    w = copy.deepcopy(cells.load_json("workloads", name))
    c = copy.deepcopy(cells.load_json("configs", w["config"]))
    kind = c["fl"]["task"]
    toy = getattr(tasks.load(kind), "toy", None)
    if toy is None:
        raise SystemExit(f"chipbench: task kind {kind!r} has no toy() "
                         f"(tasks/{kind}.py)")
    return toy(c, w)


@pytest.fixture
def toy():
    return toy_cell
