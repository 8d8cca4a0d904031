"""Shared recursive jaxpr traversal.

One walker for every invariant check that inspects jaxprs: it descends
into any eqn param that holds a sub-jaxpr (scan/while/cond bodies, pjit
calls, shard_map, pallas_call kernels, custom_jvp/vjp rules), so a rule
written against :func:`all_eqns` sees the whole program, not just the
top level.  Replaces the per-test copies that used to live in
tests/test_robust_pipeline.py and tests/test_sharded_agg.py.
"""
from __future__ import annotations

from typing import Iterator, Tuple

import jax
from jax.extend import core as jcore


def subjaxprs_of(value) -> Iterator:
    """Yield every (open) Jaxpr held by an eqn-param value: a Jaxpr, a
    ClosedJaxpr, or a list/tuple of either (e.g. cond branches)."""
    if isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from subjaxprs_of(item)


def sub_jaxprs(eqn) -> Iterator:
    """Every sub-jaxpr reachable from one eqn's params."""
    for value in eqn.params.values():
        yield from subjaxprs_of(value)


def all_eqns(jaxpr) -> Iterator[Tuple]:
    """Yield ``(jaxpr, eqn)`` for every eqn in `jaxpr` and, recursively,
    in every sub-jaxpr of every eqn.  Accepts a Jaxpr or ClosedJaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in sub_jaxprs(eqn):
            yield from all_eqns(sub)


def eqn_provenance(eqn) -> str:
    """Best-effort ``file:line (fn)`` source location of an eqn, from the
    jaxpr's recorded source_info; '?' when tracing stripped it."""
    try:
        from jax._src import source_info_util  # noqa: PLC0415
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return "?"
        return (f"{frame.file_name.rsplit('/', 1)[-1]}:{frame.start_line} "
                f"({frame.function_name})")
    except Exception:
        return "?"


def leaf_sizes(tree) -> list:
    """Element counts of the array leaves of a pytree (the size scale
    against which 'leaf-sized materialization' findings are judged)."""
    return sorted(
        int(leaf.size) for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "size"))
