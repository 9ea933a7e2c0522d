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

# The device-op name each Pallas kernel shows in a profiler trace. A custom
# call takes the name of the jitted wrapper that issues it, so renaming a
# wrapper renames the kernel in every trace and silences whatever reads it
# by name (the benchmark's roofline shares); tests pin each wrapper's
# __name__ to its entry here.
TRACE_NAMES = {
    "fused": "_fused",                # fused/ops.py: scrub + JLS residuals
    "jls.prepass": "_prepass",        # jls/entropy.py: zigzag + row sums
    "jls.len_rem": "_len_rem",        # jls/entropy.py: Rice lengths + remainders
    "jls.splice": "_splice",          # jls/entropy.py: Rice splice to payloads
    "jls.residuals": "_residuals",    # jls/ops.py: JLS residuals alone
    "textdetect": "_profiles",        # textdetect/ops.py: text-band profiles
    "scrub": "_scrub_padded",         # scrub/ops.py: rectangle blanking
    "phi_detect": "_detect",          # phi_detect/ops.py: edge density
    "bitmap": "_combine_padded",      # bitmap/ops.py: predicate combine
}


def on_cpu() -> bool:
    """True on JAX's CPU backend: kernel wrappers default to interpret mode
    and the batched executor to its host two-pass. Anywhere else the kernels
    are compiled for the device, with no fallback."""
    import jax

    return jax.default_backend() == "cpu"
