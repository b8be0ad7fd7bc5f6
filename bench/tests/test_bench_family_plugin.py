"""A detector family plugs in as new files and entries only: a copy of
the benchmark gains a family module, a configuration and a cell, and
the copy's own ``run.py``, unedited, runs that cell to ``correct`` on
this CPU -- for a family that serves one detection per anchor and for
one that suppresses each class on its own."""
import importlib.util
import json
import shutil
import sys
import types

import numpy as np
import pytest

from bench import compare, run
from bench.families import Candidates

# The program's SSD under another family name: its keys sit under
# "detector", and its heads see a 32 px frame at strides 4 and 8 with
# anchors of other scales.
FAMILY = '''"""Test family: the program's SSD read from the keys under
"detector"."""
from bench.families import ssd


def _ssd(cfg):
    return {"ssd": cfg["detector"]}


def check(cfg):
    ssd.check(_ssd(cfg))


def image_size(cfg):
    return ssd.image_size(_ssd(cfg))


def program_config(cfg):
    return ssd.program_config(_ssd(cfg))


def make_params(cfg, seed):
    return ssd.make_params(_ssd(cfg), seed)


def candidates(cfg, params, images, precision):
    return ssd.candidates(_ssd(cfg), params, images, precision)


survivors = ssd.survivors


def flops_per_frame(cfg):
    return ssd.flops_per_frame(_ssd(cfg))


def flops_by_scope(cfg):
    return ssd.flops_by_scope(_ssd(cfg))


def bytes_by_scope(cfg, frames_per_call):
    return ssd.bytes_by_scope(_ssd(cfg), frames_per_call)
'''

CONFIG = {
    "name": "altssd32",
    "source": "https://arxiv.org/abs/1512.02325",
    "family": "altssd",
    "detector": {"image_size": 32, "n_classes": 2, "channels": [8, 16, 16],
                 "feature_strides": [4, 8], "anchor_scales": [0.2, 0.45]},
    "reduced": [],
    "serving": {"n_replicas": 1, "max_micro_batch": 8, "score_thr": 0.4,
                "iou_thr": 0.5, "max_out": 32},
    "limits": {"det_gap": 2e-05, "cls_gap": 1e-05, "nms_miss": 0.001,
               "track_miss": 0.0005, "track_gap": 0.0001},
}
CELL = "altssd32-eth14-steady"

# A family that scores every class of an anchor and suppresses each
# class on its own, so that one anchor may be served once per class.
# The program it runs is the SSD with one class, whose objectness is
# that class's score: there the program's class-agnostic NMS serves
# what per-class NMS does.
PER_CLASS_FAMILY = '''"""Test family: per-class scores and per-class
greedy NMS over the program's one-class SSD."""
import numpy as np

from bench import reference
from bench.families import Candidates, Rows, ssd

check = ssd.check
image_size = ssd.image_size
program_config = ssd.program_config
make_params = ssd.make_params
flops_per_frame = ssd.flops_per_frame
flops_by_scope = ssd.flops_by_scope
bytes_by_scope = ssd.bytes_by_scope


def candidates(cfg, params, images, precision):
    return [Candidates(c.boxes, c.scores[:, None], None)
            for c in ssd.candidates(cfg, params, images, precision)]


def survivors(cand, serve):
    found = []
    for c in range(cand.scores.shape[1]):
        keep = reference.nms(cand.boxes, cand.scores[:, c],
                             score_thr=serve["score_thr"],
                             iou_thr=serve["iou_thr"],
                             max_out=serve["max_out"])
        found += [(-cand.scores[a, c], int(a), c) for a in keep]
    found = sorted(found, key=lambda r: r[0])[:serve["max_out"]]
    a = np.array([r[1] for r in found], np.int64)
    c = np.array([r[2] for r in found], np.int64)
    return Rows(a, c, cand.boxes[a].reshape(-1, 4), cand.scores[a, c])
'''

PER_CLASS_CONFIG = dict(
    {k: v for k, v in CONFIG.items() if k != "detector"}, name="pcssd32",
    family="pcssd", ssd=dict(CONFIG["detector"], n_classes=1))
PER_CLASS_CELL = "pcssd32-eth14-steady"


def _copy(tmp_path, monkeypatch, family, source, config, cell):
    """A copy of the benchmark with one family, configuration and cell
    added, and its ``run`` module loaded from the copy; ``sys.path`` is
    given back afterwards."""
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "families" / f"{family}.py").write_text(source)
    name = config["name"]
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(config))
    bench["configs"].append({
        "name": name, "source": config["source"],
        "file": f"bench/configs/{name}.json", "reduced": [],
        "why": "a family added as a file"})
    bench["workloads"].append({
        "name": cell, "config": name, "traffic": "eth14-steady",
        "chips": 1, "why": "a cell added as an entry"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_run", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def jax_config_kept():
    """``run_cell`` configures JAX for a benchmark process; give the
    next test the configuration it had."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


@pytest.fixture
def copy_run(tmp_path, monkeypatch, jax_config_kept):
    return _copy(tmp_path, monkeypatch, "altssd", FAMILY, CONFIG, CELL)


@pytest.fixture
def per_class_copy_run(tmp_path, monkeypatch, jax_config_kept):
    return _copy(tmp_path, monkeypatch, "pcssd", PER_CLASS_FAMILY,
                 PER_CLASS_CONFIG, PER_CLASS_CELL)


def test_new_family_config_and_cell_run_correct(copy_run):
    before = _bench_files()
    assert copy_run.BENCH != run.BENCH
    cell = copy_run.Cell(CELL, cameras=2)
    assert cell.family.program_config(cell.config).image_size == 32
    out = copy_run.run_cell(CELL, 2**31 + 901, 1.0, False, cameras=2,
                            check_device=False, log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 2 * 14 and out["failed"] == 0
    assert out["window"]["detected"] > 0
    assert set(out["checks"]) == {"failed", "det_gap", "cls_gap", "nms_miss",
                                  "track_miss", "track_gap"}
    assert _bench_files() == before


def _bench_files():
    return sorted(p.relative_to(run.ROOT) for p in run.BENCH.rglob("*")
                  if "__pycache__" not in p.parts)


def test_family_that_suppresses_per_class_runs_correct(per_class_copy_run):
    before = _bench_files()
    cell = per_class_copy_run.Cell(PER_CLASS_CELL, cameras=2)
    assert cell.family.program_config(cell.config).n_classes == 1
    out = per_class_copy_run.run_cell(PER_CLASS_CELL, 2**31 + 903, 1.0,
                                      False, cameras=2, check_device=False,
                                      log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["window"]["detected"] > 0
    assert out["checks"]["cls_gap"]["value"] == 0.0
    assert _bench_files() == before


def _per_class_family():
    mod = types.ModuleType("pcssd")
    exec(PER_CLASS_FAMILY, mod.__dict__)
    return mod


# three anchors, two classes: anchor 1 overlaps anchor 0 and loses to
# it in class 0; anchor 0 survives in both classes, anchor 2 in class 1
BOXES = np.array([[0.1, 0.1, 0.5, 0.5], [0.12, 0.1, 0.52, 0.5],
                  [0.6, 0.6, 0.9, 0.9]])
SCORES = np.array([[0.9, 0.7], [0.8, 0.1], [0.2, 0.6]])
SERVE = {"score_thr": 0.4, "iou_thr": 0.5, "max_out": 4}


def _served(rows, change=None):
    bx, sc, cl = rows.boxes.copy(), rows.scores.copy(), rows.cls.copy()
    va = np.ones(len(cl), bool)
    if change is not None:
        change(bx, sc, cl, va)
    return [(bx, sc, cl, va)]


def test_per_class_rows_serve_an_anchor_once_per_class():
    fam = _per_class_family()
    cand = Candidates(BOXES, SCORES, None)
    rows = fam.survivors(cand, SERVE)
    assert rows.anchor.tolist() == [0, 0, 2]
    assert rows.cls.tolist() == [0, 1, 1]
    assert rows.scores.tolist() == [0.9, 0.7, 0.6]
    nums = compare.detector_numbers(_served(rows), [cand], SERVE,
                                    fam.survivors)
    assert nums == {"det_gap": 0.0, "cls_gap": 0.0, "nms_miss": 0.0}


def test_per_class_numbers_see_a_class_altered_or_dropped():
    fam = _per_class_family()
    cand = Candidates(BOXES, SCORES, None)
    rows = fam.survivors(cand, SERVE)

    def flip(bx, sc, cl, va):
        cl[2] = 0            # anchor 2 served as class 0, scored 0.6

    def drop(bx, sc, cl, va):
        va[1] = False        # anchor 0's class-1 detection left out

    def unknown(bx, sc, cl, va):
        cl[0] = 2            # a class the detector does not have

    nums = compare.detector_numbers(_served(rows, flip), [cand], SERVE,
                                    fam.survivors)
    assert nums["det_gap"] == pytest.approx(0.4)
    assert nums["nms_miss"] == pytest.approx(2 / 3)
    nums = compare.detector_numbers(_served(rows, drop), [cand], SERVE,
                                    fam.survivors)
    assert nums["det_gap"] == 0.0
    assert nums["nms_miss"] == pytest.approx(1 / 3)
    nums = compare.detector_numbers(_served(rows, unknown), [cand], SERVE,
                                    fam.survivors)
    # anchor 0's class-0 survivor unmatched, and the unknown class
    assert nums["nms_miss"] == pytest.approx(2 / 3)
