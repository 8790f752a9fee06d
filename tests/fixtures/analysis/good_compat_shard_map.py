"""Analyzer fixture: a CLEAN checked-shard_map compat wrapper.

A copy of the wrapper parallel/mesh.py carried while the repo still ran on
a jax without ``jax.shard_map`` (deleted with that jax). It stays here as
the text tests/test_spmd_analysis.py mutates to prove the sharding pass
has teeth: flip ``check_rep=True,`` and SHD004 must fire; strip the
``sharding-ok`` waiver off the explicit opt-out forward and SHD004 must
resurface. As written it gates clean.
"""

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map as _experimental_smap
from jax.sharding import PartitionSpec as P


def _assert_replicated(x, axes):
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jax.lax.pmean(x, axes)
    return jax.lax.pmax(x, axes)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    import jax.tree_util as jtu

    if check_vma is False:
        # The caller explicitly opted out of replication checking; the
        # identity-collective wrapping below exists only to SATISFY the
        # checker, so it is skipped along with it.
        # lint: sharding-ok(explicit check_vma=False forward: caller opted out; wrapping exists only to satisfy the checker being disabled)
        return _experimental_smap(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_rep=False,
        )

    axis_names = tuple(mesh.axis_names)

    def wrapped(*args):
        out = f(*args)
        spec_leaves, spec_def = jtu.tree_flatten(
            out_specs, is_leaf=lambda s: isinstance(s, P)
        )
        subtrees = spec_def.flatten_up_to(out)
        fixed = []
        for spec, sub in zip(spec_leaves, subtrees):
            named = set()
            for entry in spec:
                if entry is None:
                    continue
                if isinstance(entry, str):
                    named.add(entry)
                else:
                    named.update(entry)
            missing = tuple(n for n in axis_names if n not in named)
            if missing:
                sub = jax.tree.map(
                    lambda x: _assert_replicated(jnp.asarray(x), missing),
                    sub,
                )
            fixed.append(sub)
        return jtu.tree_unflatten(spec_def, fixed)

    return _experimental_smap(
        wrapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_rep=True,
    )
