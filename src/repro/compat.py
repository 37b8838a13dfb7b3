"""The one ``shard_map`` entry point every SPMD call site uses."""

from __future__ import annotations

from typing import Any, Callable

import jax


def shard_map_compat(
    f: Callable, *, mesh: Any, in_specs: Any, out_specs: Any
) -> Callable:
    """``jax.shard_map`` with the static replication check off: the device
    functions slice by ``axis_index``, which that check cannot follow."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
