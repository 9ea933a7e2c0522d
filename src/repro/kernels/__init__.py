# Pallas TPU kernels for the de-identification compute hot-spots.
#   scrub      — batched PHI rectangle blanking (the paper's scrub stage)
#   phi_detect — burned-in-text detector (paper Future Work: OCR/ML, TPU-adapted)
#   jls        — JPEG-Lossless predictor residuals (TPU half of the codec)
#   fused      — single-pass scrub+JLS (DESIGN.md §4)
#   bitmap     — packed-bitmap predicate combine + popcount (catalog queries)
#   textdetect — tile-wise text-band profiles for the burned-in-PHI
#                detector's registry fallback (DESIGN.md §9; numpy ref.py
#                is bit-identical, not just allclose)
# Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper: interpret mode on the CPU backend, compiled on an accelerator) and
# ref.py (numpy/jnp oracle).


def on_cpu() -> bool:
    """True on JAX's CPU backend: kernel wrappers default to interpret mode
    and the batched executor to its host two-pass. Anywhere else the kernels
    are compiled for the device, with no fallback."""
    import jax

    return jax.default_backend() == "cpu"
