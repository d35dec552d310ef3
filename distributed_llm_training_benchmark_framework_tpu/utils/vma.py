"""Varying-manual-axes (vma) helpers.

Inside a partially-manual ``jax.shard_map`` every value's type tracks which
manual mesh axes it varies over; zeros initializers, scan carries and Pallas
out_shapes must declare vma that matches what the computation produces or the
checker rejects the program. These helpers centralize the introspection so a
JAX rename of the ``vma`` aval attribute or the ``pcast`` signature is a
one-file fix.
"""

from __future__ import annotations

from typing import FrozenSet, Iterable

import jax
from jax import lax


def vma_of(*arrays) -> FrozenSet[str]:
    """Union of the manual mesh axes the given values vary over."""
    axes = set()
    for a in arrays:
        axes |= set(getattr(jax.typeof(a), "vma", ()) or ())
    return frozenset(axes)


def pcast_missing(x, axes: Iterable[str]):
    """pcast ``x`` to vary over ``axes``, skipping axes it already varies
    over (pcast rejects varying->varying). The transpose of
    invariant->varying is a psum, which the pipeline backward passes lean
    on (e.g. the 1F1B embed vjp)."""
    have = vma_of(x)
    need = tuple(a for a in axes if a not in have)
    return lax.pcast(x, need, to="varying") if need else x


def pcast_like(x, *like):
    """pcast ``x`` to vary over every axis any of ``like`` varies over."""
    return pcast_missing(x, sorted(vma_of(*like)))
