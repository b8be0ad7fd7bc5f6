"""Every file BENCHMARK.json names loads by name, and the file keeps to
the shape the harness relies on."""
import importlib.util
import json
import re

import pytest

from bench import generator, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
E2E = {m["name"] for m in BENCH["end_to_end"]}


CONFIG_FILES = sorted((run.BENCH / "configs").glob("*.json"))
LISTED = {c["file"]: c for c in BENCH["configs"]}


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_loads(path):
    """Every configuration file, listed in BENCHMARK.json or kept for a
    cell to come, loads and has its limits."""
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    conf = LISTED.get(str(path.relative_to(run.ROOT)))
    if conf is not None:
        assert cfg["name"] == conf["name"]
        assert cfg["source"] == conf["source"]
        assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    ssd = cfg["ssd"]
    assert len(ssd["feature_strides"]) == len(ssd["anchor_scales"]) == 2
    assert set(cfg["serving"]) == {"n_replicas", "max_micro_batch",
                                   "score_thr", "iou_thr", "max_out"}
    limits = run.load_limits(cfg["name"])
    assert set(limits) == {"det_gap", "cls_gap", "nms_miss", "track_miss",
                           "track_gap"}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads(cell):
    c = run.Cell(cell["name"])
    assert c.mix.cameras > 0 and c.mix.pool_frames > 0
    # one frame per camera per emit boundary keeps the tracker width fixed
    assert abs(c.mix.emit_period_s * c.mix.fps - 1.0) < 1e-9
    e2e = {m["name"] for m in c.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.metrics("per_layer")


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads(metric):
    path = run.BENCH / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    assert NAME.match(metric["name"])
    if metric in BENCH["per_layer"]:
        assert metric["moves"] in E2E
        for w in metric.get("workloads", []):
            cell = run.Cell(w)
            assert metric["moves"] in {m["name"]
                                       for m in cell.metrics("end_to_end")}


def test_traffic_files_are_all_used():
    used = {w["traffic"] for w in BENCH["workloads"]}
    files = {p.stem for p in (run.BENCH / "traffic").glob("*.json")}
    assert used == files
    for name in used:
        generator.Mix.from_dict(name, json.loads(
            (run.BENCH / "traffic" / f"{name}.json").read_text()))


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
