"""Toy widths for the benchmark's CPU tests. The cells run at published
widths only on the chip; here every width is cut so that a round takes
milliseconds, and the traffic keeps its shape (a client's batch draws
from several rows, so half of it differs from the whole)."""
import copy
import os

import pytest

from chipbench import cells

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 12345      # past 32 signed bits, as the driver's are
# every traffic mix the benchmark holds, listed in BENCHMARK.json or not
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(cells.HERE,
                                                       "workloads"))
               if f.endswith(".json"))


def toy_cell(name: str) -> tuple:
    w = copy.deepcopy(cells.load_json("workloads", name))
    c = copy.deepcopy(cells.load_json("configs", w["config"]))
    task = w["task"]
    if c["fl"]["task"] == "classify":
        c["model"].update(vocab_size=512, d_model=32, head_dim=16, d_ff=64,
                          max_positions=16)
        w["data"].update(seq_len=16, n_train=2000, local_steps=2, batch=4)
        task.update(clients=min(task["clients"], 3000),
                    clients_per_round=min(task["clients_per_round"], 16))
        if task["wave_size"]:
            task.update(wave_size=4, wave_clients=task["clients_per_round"])
        if task.get("drop_per_round"):
            task.update(drop_per_round=2)
    else:
        c["model"].update(num_layers=1, num_encoder_layers=1, d_model=64,
                          num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
                          vocab_size=512)
        w["data"].update(enc_frames=32, dec_tokens=16, frame_pool=8)
        task.update(clients=16, clients_per_round=4)
    return c, w


@pytest.fixture
def toy():
    return toy_cell
