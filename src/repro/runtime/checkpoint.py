"""Minimal dependency-free checkpointing (orbax is not available offline).

Saves a pytree as one .npz per top-level key plus a JSON manifest with the
tree structure; restores onto host then (optionally) re-shards via
device_put with the caller's shardings.  Atomic via tmp-dir rename.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    return {jax.tree_util.keystr(kp): np.asarray(x) for kp, x in flat}, \
        treedef


def save_checkpoint(path: str | Path, tree: Any, step: int = 0):
    path = Path(path)
    tmp = path.with_name(path.name + ".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    leaves, _ = _flatten(tree)
    np.savez(tmp / "arrays.npz", **leaves)
    manifest = {"step": step, "keys": sorted(leaves)}
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if path.exists():
        shutil.rmtree(path)
    tmp.rename(path)
    return path


def restore_checkpoint(path: str | Path, like: Any,
                       shardings: Optional[Any] = None):
    """Restore into the structure of ``like``; arrays placed with
    ``shardings`` when given (mesh-sharded restore)."""
    path = Path(path)
    data = np.load(path / "arrays.npz")
    flat, treedef = jax.tree.flatten_with_path(like)
    out = []
    for kp, ref in flat:
        key = jax.tree_util.keystr(kp)
        arr = data[key]
        assert arr.shape == tuple(ref.shape), (key, arr.shape, ref.shape)
        out.append(arr.astype(ref.dtype))
    tree = jax.tree.unflatten(treedef, out)
    if shardings is not None:
        tree = jax.device_put(tree, shardings)
    return tree


def checkpoint_step(path: str | Path) -> int:
    return json.loads((Path(path) / "manifest.json").read_text())["step"]
