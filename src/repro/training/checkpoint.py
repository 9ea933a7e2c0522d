"""Checkpoint/restart for the training plane (DESIGN.md §5).

Chunked-npz layout, crash-safe by construction:

  step_000123/
    meta.json        # step, tree structure, dtypes, shapes, config digest
    arrays.npz       # flat leaves keyed by tree path
  LATEST             # atomic pointer file, written last

Writes go to a temp dir + fsync + atomic rename; the LATEST pointer flips
only after the payload is durable, so a crash mid-write can never corrupt the
restore path (the previous checkpoint stays live). keep_n retention. On
multi-host TPU this would shard-save per host; here the host gathers (noted
in DESIGN.md §5 — the layout is already per-leaf so the swap is local).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
from pathlib import Path
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _flatten_with_paths(tree: Any):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = leaf
    return out, treedef


class CheckpointManager:
    def __init__(self, directory: str | os.PathLike, keep_n: int = 3) -> None:
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep_n = keep_n

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: Any, extra: Optional[dict] = None) -> Path:
        leaves, treedef = _flatten_with_paths(state)
        arrays = {}
        dtypes = {}
        for k, v in leaves.items():
            arr = np.asarray(v)
            dtypes[k] = str(arr.dtype)
            if arr.dtype not in (np.float32, np.float64, np.int32, np.int64,
                                 np.uint8, np.uint16, np.uint32, np.int8, np.int16, np.bool_):
                # npz can't round-trip ml_dtypes (bfloat16 etc.): store raw bits
                arr = arr.view(np.uint16 if arr.dtype.itemsize == 2 else np.uint8)
            arrays[k] = arr
        meta = {
            "step": int(step),
            "treedef": str(treedef),
            "keys": sorted(arrays.keys()),
            "dtypes": dtypes,
            "extra": extra or {},
        }

        final = self.dir / f"step_{step:08d}"
        tmp = Path(tempfile.mkdtemp(prefix=".ckpt_tmp_", dir=self.dir))
        try:
            np.savez(tmp / "arrays.npz", **arrays)
            (tmp / "meta.json").write_text(json.dumps(meta))
            for f in tmp.iterdir():  # fsync payload before the rename
                with open(f, "rb") as fh:
                    os.fsync(fh.fileno())
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._write_latest(final.name)
        self._gc()
        return final

    def _write_latest(self, name: str) -> None:
        tmp = self.dir / ".LATEST.tmp"
        tmp.write_text(name)
        with open(tmp) as fh:
            os.fsync(fh.fileno())
        os.replace(tmp, self.dir / "LATEST")

    def _gc(self) -> None:
        ckpts = sorted(p for p in self.dir.iterdir() if p.name.startswith("step_"))
        for old in ckpts[: -self.keep_n]:
            shutil.rmtree(old, ignore_errors=True)

    # --------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        ptr = self.dir / "LATEST"
        if not ptr.exists():
            return None
        name = ptr.read_text().strip()
        if not (self.dir / name / "meta.json").exists():
            return None
        return int(name.split("_")[1])

    def restore(self, template: Any, step: Optional[int] = None) -> Tuple[Any, int, dict]:
        """Restore into the structure of ``template`` (shapes/dtypes checked)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {self.dir}")
        path = self.dir / f"step_{step:08d}"
        meta = json.loads((path / "meta.json").read_text())
        with np.load(path / "arrays.npz") as npz:
            arrays = {k: npz[k] for k in npz.files}
        leaves, treedef = _flatten_with_paths(template)
        restored = {}
        saved_dtypes = meta.get("dtypes", {})
        for key, tmpl in leaves.items():
            if key not in arrays:
                raise KeyError(f"checkpoint missing leaf {key!r}")
            arr = arrays[key]
            t = jnp.asarray(tmpl)
            if tuple(arr.shape) != tuple(t.shape):
                raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} vs template {t.shape}")
            saved = saved_dtypes.get(key, str(arr.dtype))
            if str(arr.dtype) != saved:
                # raw-bits roundtrip (e.g. bfloat16 stored as uint16): the
                # saved dtype must match the template's for exact restore
                if saved != str(t.dtype):
                    raise ValueError(f"dtype mismatch for {key}: ckpt {saved} vs template {t.dtype}")
                arr = arr.view(np.dtype(t.dtype))  # ml_dtypes registers with numpy
            restored[key] = jnp.asarray(arr, t.dtype)
        flat_t, td = jax.tree.flatten(template)
        keys_in_order = list(_flatten_with_paths(template)[0].keys())
        new_leaves = [restored[k] for k in keys_in_order]
        return jax.tree.unflatten(td, new_leaves), meta["step"], meta.get("extra", {})
