"""Client data for a cell, made on the host from the seed during set-up.

The generator is the task kind's (``tasks/<kind>.py``, ``Traffic``); it
reads the ``data`` block of a workload file. ``batch_fn(cid, round_idx)``
only indexes what set-up made: it is deterministic in (seed, cid,
round_idx) and returns leaves shaped ``(local_steps, batch, ...)``, the
program's ``CohortEngine`` contract.
"""
from __future__ import annotations

import zlib

import numpy as np


def seed_words(seed: int, n: int, stream: int = 0) -> list:
    """``n`` uint32 words of ``stream`` from any non-negative integer seed
    (``--seed`` may exceed 32 bits)."""
    return [int(w) for w in
            np.random.SeedSequence([int(seed), stream]).generate_state(n)]


def client_index(cid) -> int:
    tail = str(cid).rsplit("-", 1)[-1]
    return int(tail) if tail.isdigit() else zlib.crc32(str(cid).encode())


def round_rng(word: int, cid, round_idx: int) -> np.random.RandomState:
    """The generator of one client's data in one round."""
    return np.random.RandomState(
        (word + round_idx * 131071 + client_index(cid) * 131 + 7) % (2**31 - 1))


def make_traffic(kind: str, data: dict, model: dict, seed: int):
    """Task kind ``kind``'s generator for a workload's ``data`` block;
    ``model`` is the configuration's ``model`` block (widths as run)."""
    from chipbench import tasks
    return tasks.load(kind).Traffic(data, model, seed)
