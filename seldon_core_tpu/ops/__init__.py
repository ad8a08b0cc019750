"""TPU-native ops: ring attention (sequence-parallel long context), sampling,
and pallas kernels. No reference counterpart — the reference is a serving
platform with no model/kernel code (SURVEY.md §5 'Long-context: absent,
design from scratch')."""


def pallas_interpret_default() -> bool:
    """How a Pallas kernel runs when its caller did not say: compiled by
    Mosaic on a TPU, under the Pallas interpreter on every other backend.

    The backend's platform is a host fact — the answer is the same inside
    and outside a ``jax.jit`` trace — and no XLA reference ever stands in
    for the kernel: a TPU that cannot compile it raises the compiler's
    error at the call site instead of serving something else."""
    import jax

    return jax.default_backend() != "tpu"
