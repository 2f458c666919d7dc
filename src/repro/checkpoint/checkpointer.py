"""Minimal npz pytree checkpointer.

HyperTrick restarts terminated hyperparameter trials from scratch (no
preemption state needed — that's the point of the algorithm), but the
training framework still checkpoints params/opt-state for fault tolerance.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Any

import jax
import numpy as np


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree.flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def save(path: str, tree: Any, metadata: dict | None = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten(tree)
    tmp = path + ".tmp"
    np.savez(tmp, **flat)
    os.replace(tmp + ".npz" if os.path.exists(tmp + ".npz") else tmp, path)
    if metadata is not None:
        with open(path + ".json", "w") as f:
            json.dump(metadata, f)


def restore(path: str, like: Any) -> Any:
    """Restore into the structure of ``like`` (a pytree of arrays)."""
    with np.load(path) as data:
        flat = {k: data[k] for k in data.files}
    paths, treedef = jax.tree.flatten_with_path(like)
    leaves = []
    for path_, leaf in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path_)
        arr = flat[key]
        assert arr.shape == leaf.shape, f"{key}: {arr.shape} != {leaf.shape}"
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree.unflatten(treedef, leaves)


def load_metadata(path: str) -> dict:
    with open(path + ".json") as f:
        return json.load(f)
