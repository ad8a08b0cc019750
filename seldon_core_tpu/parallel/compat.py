"""The single import site for ``shard_map`` and ``axis_size``.

Every caller in this tree imports from HERE:

    from seldon_core_tpu.parallel.compat import shard_map

so the next JAX rename is absorbed in one file. The tree predates
``jax.shard_map``'s ``check_vma`` keyword and keeps the old name
(``check_rep``) as its public surface; this module translates. Written for
the one installed JAX (0.9.0) — no branch for a version that is not here.

Routing through this module is ENFORCED: graftlint's ``compat-drift``
rule flags any direct ``jax.shard_map`` / ``jax.experimental.shard_map``
/ ``jax.lax.axis_size`` use outside this file (docs/static-analysis.md).
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "axis_size"]


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def axis_size(axis_name) -> int:
    """Size of a mapped mesh axis from inside a shard_map/pmap body."""
    return jax.lax.axis_size(axis_name)
