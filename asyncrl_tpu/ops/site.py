"""An identity whose lowering counts: for a choice that only the lowering
knows. A shape says at trace time which path a call can take, the platform
only when the program is lowered (``lax.platform_dependent``), and then only
the branch that is kept is lowered at all. So a branch binds this primitive
on one of its operands, and ``count(path)`` runs once per site and program
lowered, nothing on a steady call."""

from __future__ import annotations

from typing import Callable

from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir


def site_primitive(name: str, count: Callable[[str], None]) -> Primitive:
    """``p.bind(x, path=...)`` is ``x``; lowering it calls ``count(path)``."""
    p = Primitive(name)
    p.def_impl(lambda x, *, path: x)
    p.def_abstract_eval(lambda x, *, path: x)
    batching.primitive_batchers[p] = lambda args, dims, *, path: (
        p.bind(*args, path=path), dims[0])
    # the site is the primal's; a tangent passes by it
    ad.primitive_jvps[p] = lambda primals, tangents, *, path: (
        p.bind(*primals, path=path), tangents[0])

    def lowering(ctx, x, *, path):
        count(path)
        return [x]

    # not cacheable: JAX would lower sites of one shape and path once
    mlir.register_lowering(p, lowering, cacheable=False)
    return p
