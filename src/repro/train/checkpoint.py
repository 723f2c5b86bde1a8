"""Sharded, step-atomic checkpointing with elastic re-mesh restore.

Layout:  <dir>/step_<N>/
            manifest.json     pytree structure + per-leaf dtype/shape
            leaf_00000.npy    one file per leaf (host-gathered)
         <dir>/step_<N>.tmp/  staging dir — renamed only when complete, so a
                              preemption mid-save never corrupts the latest
                              checkpoint (rename is atomic on POSIX).

Restore never requires the saving mesh: leaves are loaded on host and
``jax.device_put`` re-shards them onto whatever mesh/shardings the restoring
job uses — this is the elastic re-mesh path (e.g. 512-chip save -> 256-chip
restore after losing a pod).
"""
from __future__ import annotations

import json
import pathlib
import re
import shutil
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

_STEP_RE = re.compile(r"^step_(\d+)$")

# non-native dtypes are stored as same-width uint bit patterns
_BITCAST = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8,
            "float8_e5m2": np.uint8}


def _flatten_with_paths(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        out.append((jax.tree_util.keystr(path), leaf))
    return out, treedef


def save(ckpt_dir, step: int, tree, *, keep: int = 3) -> pathlib.Path:
    """Write checkpoint for ``step``; prune to the newest ``keep``."""
    ckpt_dir = pathlib.Path(ckpt_dir)
    final = ckpt_dir / f"step_{step}"
    tmp = ckpt_dir / f"step_{step}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    flat, _ = _flatten_with_paths(tree)
    manifest = {"step": step, "leaves": []}
    for i, (path, leaf) in enumerate(flat):
        arr = np.asarray(jax.device_get(leaf))
        dtype = str(arr.dtype)
        if arr.dtype.kind == "V":  # ml_dtypes (bfloat16, fp8): store bit-cast
            dtype = str(jax.numpy.asarray(leaf).dtype)
            arr = arr.view(_BITCAST[dtype])
        fname = f"leaf_{i:05d}.npy"
        np.save(tmp / fname, arr)
        manifest["leaves"].append({"path": path, "file": fname,
                                   "dtype": dtype,
                                   "shape": list(arr.shape)})
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)  # atomic publish
    _prune(ckpt_dir, keep)
    return final


def _prune(ckpt_dir: pathlib.Path, keep: int) -> None:
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep] if keep else []:
        shutil.rmtree(ckpt_dir / f"step_{s}", ignore_errors=True)


def all_steps(ckpt_dir) -> List[int]:
    ckpt_dir = pathlib.Path(ckpt_dir)
    if not ckpt_dir.exists():
        return []
    out = []
    for p in ckpt_dir.iterdir():
        m = _STEP_RE.match(p.name)
        if m and (p / "manifest.json").exists():
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(ckpt_dir, step: int, like, *, shardings=None):
    """Load ``step`` into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs). ``shardings``: optional matching pytree of
    NamedShardings for the *restoring* mesh — the elastic re-mesh path."""
    ckpt_dir = pathlib.Path(ckpt_dir)
    src = ckpt_dir / f"step_{step}"
    manifest = json.loads((src / "manifest.json").read_text())

    flat_like, treedef = _flatten_with_paths(like)
    by_path = {e["path"]: e for e in manifest["leaves"]}
    leaves = []
    for path, leaf in flat_like:
        entry = by_path.get(path)
        if entry is None:
            raise KeyError(f"checkpoint missing leaf {path!r}")
        arr = np.load(src / entry["file"])
        if entry["dtype"] in _BITCAST:
            import ml_dtypes
            arr = arr.view(np.dtype(getattr(ml_dtypes, entry["dtype"])))
        want_shape = tuple(getattr(leaf, "shape", arr.shape))
        if tuple(arr.shape) != want_shape:
            raise ValueError(
                f"leaf {path!r}: checkpoint shape {arr.shape} != {want_shape}")
        leaves.append(arr)

    if shardings is not None:
        flat_sh = treedef.flatten_up_to(shardings) \
            if not isinstance(shardings, list) else shardings
        leaves = [jax.device_put(a, s) for a, s in zip(leaves, flat_sh)]
    else:
        leaves = [jax.numpy.asarray(a) for a in leaves]
    return treedef.unflatten(leaves)


def restore_latest(ckpt_dir, like, *, shardings=None):
    """(step, tree) for the newest checkpoint, or (None, None)."""
    step = latest_step(ckpt_dir)
    if step is None:
        return None, None
    return step, restore(ckpt_dir, step, like, shardings=shardings)
