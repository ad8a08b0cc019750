"""The hyper-connections' Sinkhorn kernel (seldon_core_tpu/ops/sinkhorn.py) as
Mosaic compiles it, against the looped body every other lowering keeps and
against the plain iteration in float64, at a decode step's rows, a chunk's and
a shape that no tile divides:

    chiprun -- python benchmarks/sinkhorn_check.py

On a v5e (my chip run, PR 31): kernel and loop differ by 1.8e-7 - 2.4e-7 (one
float32 rounding of a divide), and the kernel is the nearer to float64.
Anywhere else the kernel runs under the Pallas interpreter and is the loop bit
for bit (tests/test_reference_xing4.py holds that in tier-1).
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.transformer import sinkhorn_entrywise
    from seldon_core_tpu.ops.sinkhorn import sinkhorn

    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    iters, eps = 20, 1e-6
    for shape in [(4, 4, 32, 1), (4, 4, 1, 256), (4, 4, 3, 700)]:
        m = jnp.asarray(np.exp(rng.normal(0, 0.9, shape)).astype(np.float32))
        kernel = np.asarray(jax.jit(lambda m: sinkhorn(m, iters, eps))(m))
        loop = np.asarray(sinkhorn_entrywise(m, iters, eps))
        want = np.asarray(m, np.float64)
        for _ in range(iters):
            want = want / (want.sum(axis=1, keepdims=True) + eps)
            want = want / (want.sum(axis=0, keepdims=True) + eps)
        print(f"{shape}: kernel - loop {np.abs(kernel - loop).max():.3g}, kernel - float64 "
              f"{np.abs(kernel - want).max():.3g}, loop - float64 {np.abs(loop - want).max():.3g}",
              flush=True)
        assert np.abs(kernel - loop).max() < 1e-5


if __name__ == "__main__":
    main()
