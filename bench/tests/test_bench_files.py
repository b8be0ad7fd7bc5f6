"""Every file BENCHMARK.json names loads by name, and the file keeps to
the shape the harness relies on."""
import ast
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


FAMILY_FILES = sorted(p for p in (run.BENCH / "families").glob("*.py")
                      if p.stem != "__init__")
FAMILY_API = ("check", "image_size", "program_config", "make_params",
              "candidates", "survivors", "flops_per_frame", "flops_by_scope",
              "bytes_by_scope")


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_loads(path):
    """Every configuration file, listed in BENCHMARK.json or kept for a
    cell to come, loads and has its limits; its family checks its own
    keys."""
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    conf = LISTED.get(str(path.relative_to(run.ROOT)))
    if conf is not None:
        assert cfg["name"] == conf["name"]
        assert cfg["source"] == conf["source"]
        assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    assert NAME.match(cfg["family"])
    assert set(cfg["serving"]) == {"n_replicas", "max_micro_batch",
                                   "score_thr", "iou_thr", "max_out"}
    assert set(cfg["limits"]) == {"det_gap", "cls_gap", "nms_miss",
                                  "track_miss", "track_gap"}
    assert all(v > 0 for v in cfg["limits"].values())
    run.load_module("families", cfg["family"]).check(cfg)


def _repro_imports(node):
    return [n for n in ast.walk(node)
            if isinstance(n, ast.ImportFrom) and (n.module or "").startswith(
                "repro") or isinstance(n, ast.Import) and any(
                a.name.startswith("repro") for a in n.names)]


@pytest.mark.parametrize("path", FAMILY_FILES, ids=lambda p: p.stem)
def test_family_module_gives_the_harness_interface(path):
    """A family gives every function the harness calls, and imports the
    program in ``program_config`` alone: its reference stands apart."""
    fam = run.load_module("families", path.stem)
    assert all(callable(getattr(fam, f)) for f in FAMILY_API)
    tree = ast.parse(path.read_text())
    inside = [n for f in tree.body if isinstance(f, ast.FunctionDef)
              and f.name == "program_config" for n in _repro_imports(f)]
    assert _repro_imports(tree) == inside


@pytest.mark.parametrize("change", [
    {"feature_strides": [16, 32]},
    {"anchor_scales": [0.1, 0.2, 0.3]},
    {"image_size": 60},
    {"aspects": [1.0, 2.0]},
])
def test_ssd_family_refuses_what_the_program_cannot_run(change):
    cfg = run.Cell("minissd64-eth14-steady").config
    bad = dict(cfg, ssd=dict(cfg["ssd"], **change))
    with pytest.raises(ValueError):
        run.load_module("families", "ssd").check(bad)


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
