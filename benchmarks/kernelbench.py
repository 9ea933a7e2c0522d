"""Kernel micro-benchmarks: Pallas (interpret, CPU) vs numpy reference, plus
the TPU roofline each kernel targets. Host timings validate correctness-path
cost; the derived column reports the kernel's v5e bound (all three kernels
are HBM-streaming: bound = 819 GB/s / bytes-touched-per-byte)."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.scrub import numpy_blank
from repro.dicom import codec
from repro.kernels.fused.ops import fused_scrub_residuals
from repro.kernels.jls.ops import jls_residuals
from repro.kernels.phi_detect.ops import edge_density
from repro.kernels.scrub.ops import pack_rects, scrub_images
from repro.launch import hw

V5E = hw.peaks(hw.V5E)  # the bound columns model a v5e chip


def _time(fn, n=3):
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def main() -> list[str]:
    rng = np.random.default_rng(0)
    imgs = (rng.random((4, 512, 512)) * 4000).astype(np.uint16)
    rl = [[(0, 0, 512, 22), (300, 22, 212, 80)]] * 4
    rects = pack_rects(rl)
    jimgs = jnp.asarray(imgs)

    lines = []
    nbytes = imgs.nbytes

    t_k = _time(lambda: np.asarray(scrub_images(jimgs, rects)))
    t_n = _time(lambda: [numpy_blank(imgs[i], rl[i]) for i in range(4)])
    # scrub reads+writes each pixel once -> v5e bound = HBM/2
    lines.append(
        f"scrub_kernel,{t_k*1e6:.0f},host_MBps={nbytes/t_k/1e6:.0f};numpy_MBps={nbytes/t_n/1e6:.0f};"
        f"v5e_bound_GBps={V5E.hbm_bw/2/1e9:.0f}"
    )

    t_p = _time(lambda: np.asarray(edge_density(jimgs)))
    lines.append(
        f"phi_detect_kernel,{t_p*1e6:.0f},host_MBps={nbytes/t_p/1e6:.0f};"
        f"v5e_bound_GBps={V5E.hbm_bw/1e9:.0f}"
    )

    t_j = _time(lambda: np.asarray(jls_residuals(imgs)))
    t_c = _time(lambda: [codec.residuals(imgs[i]) for i in range(4)])
    # jls reads u16, writes s32 residuals -> 1:3 traffic
    lines.append(
        f"jls_kernel,{t_j*1e6:.0f},host_MBps={nbytes/t_j/1e6:.0f};numpy_MBps={nbytes/t_c/1e6:.0f};"
        f"v5e_bound_GBps={V5E.hbm_bw/3/1e9:.0f}"
    )

    # fused scrub+JLS: one HBM pass for both bandwidth-bound stages.
    # bytes touched per pixel (u16): staged = scrub(2r+2w) + jls(2r+4w) = 10,
    # fused = 2r + 4w = 6 -> 0.60 of the staged pair's HBM traffic, raising
    # the input-byte roofline from HBM/5 to HBM/3.
    item = imgs.dtype.itemsize
    fused_bpp = item + 4
    staged_bpp = 3 * item + 4
    t_f = _time(lambda: np.asarray(fused_scrub_residuals(jimgs, rects)))
    t_s = _time(lambda: np.asarray(jls_residuals(scrub_images(jimgs, rects))))
    lines.append(
        f"fused_scrub_jls_kernel,{t_f*1e6:.0f},host_MBps={nbytes/t_f/1e6:.0f};"
        f"staged_MBps={nbytes/t_s/1e6:.0f};traffic_ratio={fused_bpp/staged_bpp:.2f};"
        f"v5e_bound_GBps={V5E.hbm_bw*item/fused_bpp/1e9:.0f};"
        f"staged_pair_bound_GBps={V5E.hbm_bw*item/staged_bpp/1e9:.0f}"
    )
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
