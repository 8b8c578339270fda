"""Task kinds, one file each: ``tasks/<kind>.py``, named by a configuration's
``fl.task``.

A kind's file holds all that the harness needs of that kind of task:

- ``Traffic(data, model, seed)``: the client data a workload's ``data``
  block describes, made on the host from the seed; ``batch_fn(cid,
  round_idx)`` indexes it;
- ``build(cfg, config, workload, key) -> (spec, trainable, frozen)``: the
  program's ``LocalTrainSpec`` and the weights made on the device from the
  key (the only part that imports the program);
- ``reference_client(recipe, model, dtype, frozen) -> f(state, batches)``:
  the plain reference of one client's local round, returning ``(delta,
  mean loss, first gradient)``;
- ``sample_flops(model, data) -> int``: model FLOPs of one training sample.

A later change adds a kind by adding its file.
"""
import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str):
    """The module of task kind ``kind`` (``tasks/<kind>.py``)."""
    if not os.path.isfile(os.path.join(HERE, f"{kind}.py")):
        raise SystemExit(f"chipbench: no task kind {kind!r} "
                         f"(tasks/{kind}.py is missing)")
    return importlib.import_module(f"{__name__}.{kind}")
