"""Distributed scrub farm: the paper's autoscaled worker pool as a device mesh.

The paper parallelizes de-identification across cloud VMs pulling from a
queue. On TPU the equivalent data plane is a 1-D device mesh with the image
batch sharded across the ``workers`` axis via ``jax.shard_map``; each device
runs the Pallas scrub kernel on its local shard. There is **no** cross-device
communication in the hot path — scrubbing is embarrassingly parallel, which
is exactly why the paper's design scales and why the farm's roofline is pure
HBM bandwidth (DESIGN.md §3).

Host-side responsibilities (this module):
  * resolution bucketing — studies mix 512x512 CT with 2500x2048 DX; batches
    must be shape-uniform per dispatch (the paper's per-resolution rules have
    the same effect);
  * batch padding to a multiple of the mesh size, cropped after;
  * writing scrubbed pixels back into the DICOM datasets.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dicom.dataset import DicomDataset
from repro.dicom.devices import Rect
from repro.kernels.scrub.ops import pack_rects, scrub_images


def bucket_by_resolution(
    datasets: Sequence[DicomDataset],
) -> Dict[Tuple[int, int], List[int]]:
    """Group dataset indices by pixel resolution (H, W)."""
    buckets: Dict[Tuple[int, int], List[int]] = defaultdict(list)
    for i, ds in enumerate(datasets):
        if ds.pixels is not None:
            buckets[ds.pixels.shape[:2]].append(i)
    return dict(buckets)


class ScrubFarm:
    """shard_map-wrapped batched scrubbing over a 1-D ``workers`` mesh."""

    def __init__(self, devices: Sequence[jax.Device] | None = None) -> None:
        devices = list(devices) if devices is not None else jax.devices()
        self.mesh = Mesh(np.array(devices), axis_names=("workers",))
        self.n = len(devices)
        self._fns: dict = {}

    # ------------------------------------------------------------- core op
    def _sharded_fn(self, dtype, rect_count: int):
        key = (jnp.dtype(dtype).name, rect_count)
        if key not in self._fns:

            def local(images, rects):
                # per-device shard: batch slice, full images; kernel does tiles
                return scrub_images(images, rects)

            fn = jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(P("workers"), P("workers")),
                out_specs=P("workers"),
                # pallas_call's out_shape carries no varying-mesh-axes info;
                # the farm is embarrassingly parallel so nothing to check
                check_vma=False,
            )
            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def scrub_batch(self, images: np.ndarray, rect_lists: Sequence[Sequence[Rect]]) -> np.ndarray:
        """images: (N, H, W); rect_lists: ragged per-image rects. Shards the
        batch over the mesh, scrubs, returns (N, H, W)."""
        N = images.shape[0]
        rects = pack_rects(rect_lists, R=max(4, max((len(r) for r in rect_lists), default=1)))
        pad = (-N) % self.n
        if pad:
            images = np.concatenate([images, np.zeros((pad,) + images.shape[1:], images.dtype)])
            rects = np.concatenate([rects, np.zeros((pad,) + rects.shape[1:], rects.dtype)])
        sharding = NamedSharding(self.mesh, P("workers"))
        imgs_dev = jax.device_put(jnp.asarray(images), sharding)
        rects_dev = jax.device_put(jnp.asarray(rects), sharding)
        out = self._sharded_fn(images.dtype, rects.shape[1])(imgs_dev, rects_dev)
        return np.asarray(out)[:N]

    # ------------------------------------------------------- dataset plane
    def process_datasets(
        self,
        datasets: Sequence[DicomDataset],
        rects_for,
    ) -> Dict[int, List[Rect]]:
        """Scrub a heterogeneous batch of datasets in resolution buckets.

        ``rects_for(ds) -> Optional[tuple[Rect, ...]]`` is typically
        ``ScrubStage.rects_for``. Pixels are modified in place; returns
        {dataset index: applied rects} for manifest recording.
        """
        applied: Dict[int, List[Rect]] = {}
        buckets = bucket_by_resolution(datasets)
        for (H, W), idxs in buckets.items():
            todo: List[int] = []
            rl: List[List[Rect]] = []
            for i in idxs:
                rects = rects_for(datasets[i])
                if rects:
                    todo.append(i)
                    rl.append(list(rects))
                    applied[i] = list(rects)
            if not todo:
                continue
            stack = np.stack([datasets[i].pixels for i in todo])
            out = self.scrub_batch(stack, rl)
            for j, i in enumerate(todo):
                datasets[i].pixels = out[j]
        return applied
