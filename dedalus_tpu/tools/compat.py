"""
The one spelling of `shard_map` every in-repo use routes through.

The installed JAX ships the stable `jax.shard_map` (keywords `mesh=`,
`in_specs=`, `out_specs=`, `check_vma=`, `axis_names=` — the set of
MANUAL axes); this module re-exports it so call sites share one import.
"""

from jax import shard_map

__all__ = ["shard_map"]
