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
- ``sample_flops(model, data) -> int``: model FLOPs of one training sample;
- ``toy(config, workload) -> (config, workload)``: the CPU tests' shrink of
  a cell of this kind (toy widths, few clients), made in place on copies
  of the two files' contents.

A configuration (``configs/<name>.json``) names its kind in ``fl.task``.
Its top-level keys hold the published configuration as run, its
``published_widths`` map each width of its ``model`` block to the
published key it copies (``"d_model": "hidden_size"``), and a key cut to
fit the chip holds the cut value, its published value under
``published`` (``"published": {"num_hidden_layers": 28}``), and is named
in the configuration's ``reduced`` in ``BENCHMARK.json``. Only a count of
layers, of experts held, or the vocabulary may be cut.

A later change adds a kind by adding its file, and a configuration and a
cell by adding theirs and their entries in ``BENCHMARK.json``.
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
