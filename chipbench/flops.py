"""Operations and bytes a round needs, counted from shapes.

Model FLOPs count what the model's equations need, not what the program
executes: recomputation (rematerialized layers) does not count, a masked
attention score counts as a score, an embedding lookup counts nothing.
A multiply-add is two FLOPs.

- Forward of a transformer layer over ``t`` positions attending to ``s``
  keys: ``2 * t * n_matmul`` for its weight matrices, plus ``4 * t * s * d``
  for the scores and the weighted sum.
- Training: each task kind counts its own (``tasks/<kind>.py``,
  ``train_flops``): full fine-tuning is 3x the forward (the PaLM
  accounting, 6 N T plus attention); LoRA 2x the frozen matmuls and 3x
  attention.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
LIMBS = 3                   # uint32 lanes of the chain's limb state


def encoder_layer_forward(d: int, d_ff: int, t: int) -> dict:
    return {"matmul": t * (2 * 4 * d * d + 2 * 2 * d * d_ff),
            "attention": 4 * t * t * d}


def round_model_flops(config: dict, workload: dict) -> int:
    """Model FLOPs of one round's local training (every client, every
    local step); a training sample's count is the task kind's
    (``tasks/<kind>.py``, ``sample_flops``)."""
    from chipbench import tasks
    data, task = workload["data"], workload["task"]
    per_sample = tasks.load(config["fl"]["task"]).sample_flops(
        config["model"], data)
    training = int(task["clients_per_round"]) \
        - int(task.get("drop_per_round", 0))
    return training * int(data["local_steps"]) * int(data["batch"]) \
        * per_sample


def chain_bytes(n_clients: int, size: int) -> int:
    """HBM bytes the privacy chain cannot avoid in a round: read the
    ``n_clients`` x ``size`` f32 updates once, write one limb state of
    ``LIMBS`` uint32 lanes. The mask expansion (KDF) is ALU work with no
    published peak, so HBM is the only roofline stated."""
    return 4 * n_clients * size + 4 * LIMBS * size


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in ``peaks.json`` is an
    error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
