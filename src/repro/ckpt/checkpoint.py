"""Checkpointing: atomic, content-verified, mesh-elastic.

Layout:  <dir>/step_<N>/
    manifest.json      {step, keys, shapes, dtypes, sha256 per leaf, meta}
    <leaf-id>.npy      one file per pytree leaf

Design points for scale:
* leaves are written one at a time (streaming; host never needs 2x model),
* writes go to ``step_N.tmp`` then ``os.replace`` -> crash-atomic,
* restore takes *target shardings*: leaves are ``jax.device_put`` onto the
  current mesh, so a checkpoint written on a 16x16 mesh restores onto 2x16x16
  (or 1 device) unchanged -- this is the elastic-rescale path used by
  ft/runner.py and tested in tests/test_ckpt_ft.py.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil

import jax
import numpy as np


def _resolve_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _leaf_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    keys = ["/".join(str(p) for p in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return keys, leaves, treedef


def save_checkpoint(directory: str, step: int, tree, meta: dict | None = None) -> str:
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    keys, leaves, _ = _leaf_paths(tree)
    manifest = {"step": step, "leaves": [], "meta": meta or {}}
    for i, (key, leaf) in enumerate(zip(keys, leaves)):
        arr = np.asarray(jax.device_get(leaf))
        fn = f"leaf_{i:05d}.npy"
        if arr.dtype.kind == "V" or arr.dtype.name not in np.sctypeDict:
            # exotic dtypes (bfloat16 etc.): store raw bytes; the manifest
            # dtype/shape reconstructs them on load
            np.save(os.path.join(tmp, fn),
                    np.frombuffer(arr.tobytes(), dtype=np.uint8))
        else:
            np.save(os.path.join(tmp, fn), arr)
        with open(os.path.join(tmp, fn), "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        manifest["leaves"].append(
            {"key": key, "file": fn, "shape": list(arr.shape),
             "dtype": str(arr.dtype), "sha256": digest}
        )
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and not d.endswith(".tmp")
    ]
    return max(steps) if steps else None


def restore_checkpoint(directory: str, step: int, target_tree, shardings=None,
                       verify: bool = True):
    """Restore into ``target_tree``'s structure; device_put per ``shardings``
    (a matching pytree of NamedSharding or None for host arrays)."""
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    keys, leaves, treedef = _leaf_paths(target_tree)
    by_key = {e["key"]: e for e in manifest["leaves"]}
    sh_leaves = (
        treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
    )
    out = []
    for key, ref, sh in zip(keys, leaves, sh_leaves):
        entry = by_key[key]
        fpath = os.path.join(path, entry["file"])
        if verify:
            with open(fpath, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            if digest != entry["sha256"]:
                raise IOError(f"checkpoint corruption at {key}")
        arr = np.load(fpath)
        want_dtype = _resolve_dtype(entry["dtype"])
        if arr.dtype == np.uint8 and want_dtype != np.uint8:
            arr = np.frombuffer(arr.tobytes(), dtype=want_dtype).reshape(entry["shape"])
        assert list(arr.shape) == list(ref.shape), (key, arr.shape, ref.shape)
        out.append(jax.device_put(arr, sh) if sh is not None else arr)
    return treedef.unflatten(out), manifest


def prune_checkpoints(directory: str, keep: int = 3) -> None:
    if not os.path.isdir(directory):
        return
    steps = sorted(
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and not d.endswith(".tmp")
    )
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s:08d}"), ignore_errors=True)
