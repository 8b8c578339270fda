"""The benchmark's layout: ``BENCHMARK.json`` and the files it names.

Later changes add a configuration, a task kind, a traffic mix, a
per-layer metric or a layer's program by adding a file; these tests find
each by its name, the way ``run.py`` does, and hold ``BENCHMARK.json`` to
the form the harness reads. ``test_a_cell_added_by_files_alone`` adds a
configuration, a task kind and a cell to a copy of the benchmark by new
files and entries only, and runs these tests and the cell there.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import cells, flops, run, tasks
from chipbench.conftest import toy_cell

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# the model block's widths that a configuration file maps to the published
# keys they copy (``published_widths``), wherever the block has them
WIDTHS = {"d_model", "num_layers", "num_encoder_layers", "num_heads", "d_ff",
          "vocab_size"}
# the published keys a configuration may cut (``reduced``): a count of
# layers, a count of the experts held on the chip, the vocabulary. Never a
# width: no hidden, intermediate, state or head size, no ``_dim`` or
# ``_rank``, no experts per token, no shared experts.
CUTS = re.compile(r"^(?!.*shared)(\w*_layers|\w*_experts|vocab_size)$")


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_references(bench):
    configs = {c["name"] for c in bench["configs"]}
    cells_ = {w["name"] for w in bench["workloads"]}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for entry in bench["configs"] + bench["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for entry in bench["configs"]:
        assert all(NAME.match(k) for k in entry["reduced"]), entry["reduced"]
    assert len(configs) == len(bench["configs"])
    assert len(cells_) == len(bench["workloads"])
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells_)) <= cells_
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert run.metrics_for(bench["per_layer"], w["name"])
        assert len(run.metrics_for(bench["end_to_end"], w["name"])) >= 2


@pytest.mark.parametrize("kind", ["configs", "workloads"])
def test_each_entry_has_its_file(bench, kind):
    for entry in bench[kind]:
        key = "traffic" if kind == "workloads" else "name"
        data = cells.load_json(kind, entry[key])
        if kind == "workloads":
            assert data["config"] == entry["config"]
            assert set(data["limits"]) == {"median_loss_gap", "mean_loss_gap",
                                           "update_gap", "change_gap"}
        else:
            assert os.path.normpath(entry["file"]) == os.path.join(
                "chipbench", "configs", f"{entry['name']}.json")


def test_every_task_kind_found_by_file_name(bench):
    for entry in bench["configs"]:
        kind = tasks.load(cells.load_json("configs", entry["name"])["fl"]["task"])
        for part in ("Traffic", "build", "reference_client", "sample_flops",
                     "toy"):
            assert callable(getattr(kind, part)), (entry["name"], part)
    with pytest.raises(SystemExit, match="no-such-kind"):
        tasks.load("no-such-kind")


def test_a_kind_without_a_toy_is_named(monkeypatch):
    monkeypatch.setattr(tasks, "load", lambda kind: object())
    with pytest.raises(SystemExit, match=r"tasks/classify\.py"):
        toy_cell("spam-paper")


# each kind's toy shrink as the CPU tests have run it: the model block's
# keys it sets, and the whole data and task blocks it leaves
TOYS = {
    "spam-paper": (
        {"vocab_size": 512, "d_model": 32, "num_layers": 2, "num_heads": 2,
         "head_dim": 16, "d_ff": 64, "max_positions": 16},
        {"n_train": 2000, "n_splits": 100, "seq_len": 16, "local_steps": 2,
         "batch": 4},
        {"clients": 100, "clients_per_round": 16, "fleet": False,
         "wave_size": 0, "wave_clients": 0}),
    "spam-cohort128-churn": (
        {"vocab_size": 512, "d_model": 32, "num_layers": 2, "num_heads": 2,
         "head_dim": 16, "d_ff": 64, "max_positions": 16},
        {"n_train": 2000, "n_splits": 100, "seq_len": 16, "local_steps": 2,
         "batch": 4},
        {"clients": 3000, "clients_per_round": 16, "fleet": True,
         "wave_size": 4, "wave_clients": 16, "drop_per_round": 2}),
    "whisper-lora": (
        {"num_layers": 1, "num_encoder_layers": 1, "d_model": 64,
         "num_heads": 2, "num_kv_heads": 2, "head_dim": 32, "d_ff": 128,
         "vocab_size": 512},
        {"enc_frames": 32, "dec_tokens": 16, "frame_pool": 8,
         "local_steps": 1, "batch": 2},
        {"clients": 16, "clients_per_round": 4, "fleet": False,
         "wave_size": 1, "wave_clients": 0}),
}


@pytest.mark.parametrize("name", sorted(TOYS))
def test_toy_shrinks_are_pinned(name):
    config, workload = toy_cell(name)
    model, data, task = TOYS[name]
    assert {k: config["model"][k] for k in model} == model
    assert workload["data"] == data
    assert workload["task"] == task


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))


def test_reader_finding_nothing_returns_none():
    ctx = {"rounds": 0, "window_s": 0.0, "busy_s": 0.0, "spans_s": {},
           "memory": {}, "peaks": flops.peaks("TPU v5 lite"),
           "program_ns": lambda layer: 0, "round_model_flops": 0,
           "chain_bytes": 0}
    for name in os.listdir(os.path.join(HERE, "metrics")):
        if name.endswith(".py"):
            assert run.load_reader(name[:-3])(ctx) is None, name


def test_layer_programs_found_by_file_name():
    assert {"run"} <= run.layer_programs("train")
    assert {"ravel_rows", "_cohort_interims", "_wave_limb_state",
            "merge_limb_states", "dequantize_limb_state"} \
        <= run.layer_programs("chain")


def test_missing_file_is_named():
    with pytest.raises(SystemExit, match="no-such-mix"):
        cells.load_json("workloads", "no-such-mix")
    with pytest.raises(SystemExit, match="no workload"):
        run.cell_entry(run.load_benchmark(), "no-such-cell")


# the published widths of the two configurations the benchmark began with
WIDTH_PINS = {
    "bert-tiny-spam": (
        ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "intermediate_size", "vocab_size"),
        ("d_model", "num_layers", "num_heads", "d_ff", "vocab_size"),
        (128, 2, 2, 512, 30522)),
    "whisper-medium": (
        ("d_model", "encoder_layers", "decoder_layers", "encoder_ffn_dim",
         "vocab_size"),
        ("d_model", "num_encoder_layers", "num_layers", "d_ff", "vocab_size"),
        (1024, 24, 24, 4096, 51865)),
}


@pytest.mark.parametrize("name", sorted(WIDTH_PINS))
def test_config_files_keep_published_widths(bench, name):
    published, model_keys, want = WIDTH_PINS[name]
    data = cells.load_json("configs", name)
    assert tuple(data[k] for k in published) == want
    assert tuple(data["model"][k] for k in model_keys) == want
    (entry,) = [c for c in bench["configs"] if c["name"] == name]
    assert entry["reduced"] == []


def test_every_config_runs_the_widths_its_file_publishes(bench):
    """The file's top-level keys hold the configuration as run, so every
    width of the model block equals the published key it copies; a cut
    key holds the cut value, and its published value under
    ``published``."""
    for entry in bench["configs"]:
        data = cells.load_json("configs", entry["name"])
        model, pairs = data["model"], data["published_widths"]
        assert WIDTHS & set(model) <= set(pairs), entry["name"]
        for key, published in pairs.items():
            assert model[key] == data[published], (entry["name"], key)


def test_reduced_cuts_only_depth_experts_or_vocabulary(bench):
    for entry in bench["configs"]:
        data = cells.load_json("configs", entry["name"])
        cut = data.get("published", {})
        assert set(entry["reduced"]) == set(cut), entry["name"]
        for key in entry["reduced"]:
            assert CUTS.match(key), \
                f"{entry['name']}: reduced names {key!r}, a width"
            assert data[key] < cut[key], (entry["name"], key)


def test_benchmark_json_is_small_and_plain():
    path = os.path.join(run.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        json.load(f)


# a third configuration (prajjwal1/bert-mini, its 4 layers cut to 2), a
# task kind of its own and a cell, as a later change would add them
MINI_CONFIG = {
    "name": "bert-mini-spam",
    "source": "https://huggingface.co/prajjwal1/bert-mini",
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 1024, "vocab_size": 30522,
    "published": {"num_hidden_layers": 4},
    "published_widths": {"d_model": "hidden_size",
                         "num_layers": "num_hidden_layers",
                         "num_heads": "num_attention_heads",
                         "d_ff": "intermediate_size",
                         "vocab_size": "vocab_size"},
    "model": {"arch": "bert-tiny-spam", "d_model": 256, "num_layers": 2,
              "num_heads": 4, "num_kv_heads": 4, "head_dim": 64,
              "d_ff": 1024, "vocab_size": 30522, "tie_embeddings": True,
              "max_positions": 512},
    "fl": {"task": "spam_copy", "optimizer": "adamw", "lr": 0.0005,
           "vg_size": 8,
           "dp": {"mechanism": "local", "clip_norm": 0.5,
                  "noise_multiplier": 0.16, "delta": 1e-05}},
}
MINI_KIND = '''"""classify under a name of its own, with its own toy shrink."""
from chipbench.tasks.classify import (  # noqa: F401
    Traffic, build, reference_client, sample_flops)


def toy(config, workload):
    task = workload["task"]
    config["model"].update(vocab_size=256, d_model=32, head_dim=16,
                           d_ff=64, max_positions=16)
    workload["data"].update(seq_len=16, n_train=1000, local_steps=2, batch=4)
    task.update(clients=min(task["clients"], 24),
                clients_per_round=min(task["clients_per_round"], 8))
    return config, workload
'''
MINI_CELL = {
    "config": "bert-mini-spam",
    "why": "the paper's round on a deeper trunk",
    "task": {"clients": 40, "clients_per_round": 8, "fleet": False,
             "wave_size": 0, "wave_clients": 0},
    "data": {"n_train": 2000, "n_splits": 20, "seq_len": 128,
             "local_steps": 5, "batch": 8},
    "trace_rounds": 10,
    "limits": {"median_loss_gap": 0.03, "mean_loss_gap": 0.035,
               "update_gap": 0.0007, "change_gap": 0.001},
}


def _write_json(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def _pytest(root, *args) -> subprocess.CompletedProcess:
    """pytest in ``root``, a copy of the benchmark, with the program from
    this checkout's ``src``."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "PYTHONPATH"))}
    env.update(PYTHONPATH=os.path.join(run.ROOT, "src"), JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         *args], cwd=root, env=env, capture_output=True, text=True,
        timeout=600)


def test_a_cell_added_by_files_alone(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    here = root / "chipbench"
    _write_json(here / "configs" / "bert-mini-spam.json", MINI_CONFIG)
    (here / "tasks" / "spam_copy.py").write_text(MINI_KIND)
    _write_json(here / "workloads" / "mini-spam.json", MINI_CELL)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "bert-mini-spam", "source": MINI_CONFIG["source"],
        "file": "chipbench/configs/bert-mini-spam.json",
        "reduced": ["num_hidden_layers"], "why": "a deeper trunk"})
    bench["workloads"].append({
        "name": "mini-spam", "config": "bert-mini-spam",
        "traffic": "mini-spam", "chips": 1, "why": MINI_CELL["why"]})
    for m in bench["per_layer"]:
        m["workloads"].append("mini-spam")
    _write_json(root / "BENCHMARK.json", bench)

    proc = _pytest(root, "chipbench/test_chipbench_layout.py",
                   "chipbench/test_chipbench_cells.py::"
                   "test_tiny_run_is_correct[mini-spam]",
                   "-k", "not files_alone")
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert "test_tiny_run_is_correct[mini-spam] PASSED" in proc.stdout
    assert "test_reduced_cuts_only_depth_experts_or_vocabulary PASSED" \
        in proc.stdout

    # the same copy with a width cut as well: the layout check fails
    config = dict(MINI_CONFIG, hidden_size=128,
                  published={"num_hidden_layers": 4, "hidden_size": 256},
                  model=dict(MINI_CONFIG["model"], d_model=128, head_dim=32))
    _write_json(here / "configs" / "bert-mini-spam.json", config)
    bench["configs"][-1]["reduced"].append("hidden_size")
    _write_json(root / "BENCHMARK.json", bench)
    proc = _pytest(root, "chipbench/test_chipbench_layout.py", "-k",
                   "widths or reduced")
    assert proc.returncode == 1, proc.stdout[-4000:]
    assert "test_reduced_cuts_only_depth_experts_or_vocabulary FAILED" \
        in proc.stdout
    assert "reduced names 'hidden_size', a width" in proc.stdout
    assert "test_every_config_runs_the_widths_its_file_publishes PASSED" \
        in proc.stdout
