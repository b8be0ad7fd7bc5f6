"""``chip_smoke.py``'s reference check, run on the CPU: the served
detect program of a ``DetectionEngine`` against ``decode_detections``
on the host, as the script runs it on a chip after its served trace."""
import importlib.util
from pathlib import Path

import jax

from repro.detector import SSDConfig, init_ssd
from repro.serving import DetectionEngine

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reference_check_runs_on_the_served_engine(capsys):
    cs = _chip_smoke()
    cfg = SSDConfig()
    eng = DetectionEngine(cfg=cfg, params=init_ssd(cfg, jax.random.PRNGKey(0)),
                          max_micro_batch=8, score_thr=cs.SCORE_THR,
                          iou_thr=cs.IOU_THR, max_out=cs.MAX_OUT)
    cs.reference_check(eng, cs.make_trace(cfg.image_size), cfg, n_batches=1)
    assert "reference: batches=1 " in capsys.readouterr().out
