"""The benchmark's layout: ``BENCHMARK.json`` and the files it names.

Later changes add a configuration, a traffic mix, a per-layer metric or a
layer's program by adding a file; these tests find each by its name, the
way ``run.py`` does, and hold ``BENCHMARK.json`` to the form the harness
reads.
"""
import json
import os
import re

import pytest

from chipbench import cells, flops, run, tasks

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


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
        for part in ("Traffic", "build", "reference_client", "sample_flops"):
            assert callable(getattr(kind, part)), (entry["name"], part)
    with pytest.raises(SystemExit, match="no-such-kind"):
        tasks.load("no-such-kind")


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


def test_config_files_keep_published_widths(bench):
    for entry in bench["configs"]:
        data = cells.load_json("configs", entry["name"])
        m = data["model"]
        if entry["name"] == "bert-tiny-spam":
            assert (data["hidden_size"], data["num_hidden_layers"],
                    data["num_attention_heads"], data["intermediate_size"],
                    data["vocab_size"]) == (128, 2, 2, 512, 30522)
            assert (m["d_model"], m["num_layers"], m["num_heads"], m["d_ff"],
                    m["vocab_size"]) == (128, 2, 2, 512, 30522)
        else:
            assert (data["d_model"], data["encoder_layers"],
                    data["decoder_layers"], data["encoder_ffn_dim"],
                    data["vocab_size"]) == (1024, 24, 24, 4096, 51865)
            assert (m["d_model"], m["num_encoder_layers"], m["num_layers"],
                    m["d_ff"], m["vocab_size"]) == (1024, 24, 24, 4096, 51865)
        assert entry["reduced"] == []


def test_benchmark_json_is_small_and_plain():
    path = os.path.join(run.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        json.load(f)
