"""BENCHMARK.json and the files its names point to: every configuration,
traffic mix, cell limit file and metric reader loads, names keep to the
allowed characters, and the harness needs no per-cell code."""
import json

import pytest

from bench import spec
from bench.spec import BENCH_DIR, ROOT

BENCH = spec.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]]
                         + CELLS + [m["name"] for m in METRICS]
                         + sorted({c["traffic"] for c in BENCH["workloads"]}))
def test_names_use_allowed_characters(name):
    assert spec.check_name(name, "entry") == name


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_loads_and_builds(entry):
    cfgd = spec.load_config(BENCH, entry["name"])
    assert entry["file"].startswith("bench/configs/")
    assert set(entry["reduced"]) == set(cfgd["reduced"])
    for key in cfgd["reduced"]:
        assert key in cfgd and cfgd[key] != cfgd["published"][key]
    cfg = spec.model_config(cfgd)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (
        cfgd["n_layers"], cfgd["d_model"], cfgd["vocab"])
    assert cfg.retro.attn_impl == cfgd["retro"]["attn_impl"]
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    assert cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = spec.load_traffic(cell["traffic"])
    eng = traffic["engine"]
    assert max(traffic["prompt_lens"]) <= eng["max_context"]
    assert all(o >= 1 for o in traffic["output_lens"])
    assert max(traffic["output_lens"]) <= eng["gen_headroom"]
    assert isinstance(traffic["blocks"], int) and traffic["blocks"] >= 1
    assert 0 <= traffic["trace"]["from_share"] < 1
    assert 0 < traffic["trace"]["seconds"] <= BENCH["run_seconds"]
    limits = spec.load_cell_limits(cell["name"])
    assert limits and set(limits) <= {"max_logit_gap", "mean_logit_gap"}
    assert all(v > 0 for v in limits.values())
    cfgd = spec.load_config(BENCH, cell["config"])
    if traffic["patch_pool"]:
        assert cfgd["num_patch_tokens"] < min(traffic["prompt_lens"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_reader_and_fields(metric):
    keys = {"name", "unit", "better", "source"}
    assert keys <= set(metric)
    assert metric["better"] in ("lower", "higher")
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert {"layer", "moves"} <= set(metric)
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        for cell in metric.get("workloads", []):
            assert cell in CELLS
    assert callable(spec.load_reader(metric["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, cell, False)]
    layer = spec.cell_metrics(BENCH, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    # each per-layer metric moves an end-to-end metric the cell reports
    assert {m["moves"] for m in layer} <= set(e2e)


def test_harness_has_no_per_cell_code():
    for path in sorted(BENCH_DIR.glob("*.py")):
        text = path.read_text()
        for name in CELLS + [c["name"] for c in BENCH["configs"]]:
            assert name not in text, f"{path.name} names {name}"


def test_command_stays_inside_paths():
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert (ROOT / word).is_file()
