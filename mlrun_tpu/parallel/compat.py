"""``shard_map`` in the repo's calling convention.

The context/pipeline-parallel paths are written against ``jax.shard_map``
(keyword-only, ``check_vma``, partial-manual via ``axis_names=``); this
wrapper adds the ``functools.partial`` decorator form both ops/ and
parallel/ use.
"""

from __future__ import annotations

import functools

import jax


def shard_map(f=None, *, mesh, in_specs, out_specs, check_vma=True,
              axis_names=None):
    """``jax.shard_map`` usable directly or as a ``functools.partial``
    decorator. ``axis_names`` is the set of MANUAL axes."""
    if f is None:
        return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=check_vma,
                                 axis_names=axis_names)
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)
