"""DFM mesh bridge: the mpi-list bulk operations lowered onto a jax mesh.

A "mesh DFM" is a pytree of arrays whose leading dim is the global list
index, sharded over the mesh `data` axis with the paper's contiguous-block
partition (NamedSharding produces exactly that layout).  The mpi-list ops
map onto jax-native constructs:

    map         -> jit(vmap(f))        (elementwise over the sharded dim)
    reduce      -> jit(sum/monoid)     (psum via sharding propagation)
    scan        -> lax.associative_scan (cross-shard prefix handled by XLA)
    repartition -> resharding to the balanced partition (all-to-all-ish)
    group       -> fixed-size bucket exchange (sort + reshard)

This is the sense in which the framework's data-parallel inner loop *is*
mpi-list: `train_step` = dfm.map(grad) . dfm.reduce(+).

Each verb runs under a `mesh.<verb>` profiler span, and the function it
jits is named `mesh_<verb>`, so JAX's dispatch span (`PjitFunction(...)`)
and the program (`jit_mesh_<verb>`) name the verb in a device trace.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine.tracing import span


def data_sharding(mesh, ndim: int):
    """Rows over `data` (and `pod`), on an Auto-typed copy of `mesh`: XLA's
    partitioner then inserts the cross-shard exchanges that reduce, scan
    and group need.  Explicit axes (`jax.make_mesh`'s default) refuse a
    strided slice or a gather across shards instead."""
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return NamedSharding(auto, P(axes, *([None] * (ndim - 1))))


def iterates(mesh, N: int) -> jax.Array:
    x = jnp.arange(N)
    return jax.device_put(x, data_sharding(mesh, 1))


def scatter(mesh, x) -> jax.Array:
    x = jnp.asarray(x)
    return jax.device_put(x, data_sharding(mesh, x.ndim))


def dfm_map(mesh, f: Callable, dfm, *, donate: bool = False):
    def mesh_map(x):
        return jax.vmap(f)(x)
    with span("mesh.map"):
        return jax.jit(mesh_map, donate_argnums=(0,) if donate else ())(dfm)


def dfm_reduce(mesh, f_monoid: Callable, dfm):
    """Tree-reduction over the global list with an associative monoid
    (cross-shard combine becomes a psum-like collective via GSPMD)."""
    def pairwise(v):
        n = v.shape[0]
        if n == 1:
            return v[0]
        if n % 2:
            return f_monoid(pairwise(v[:-1]), v[-1])
        return pairwise(f_monoid(v[0::2], v[1::2]))

    def mesh_reduce(x):
        return jax.tree_util.tree_map(pairwise, x)
    with span("mesh.reduce"):
        return jax.jit(mesh_reduce)(dfm)


def dfm_sum(mesh, dfm):
    def mesh_sum(x):
        return jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0), x)
    with span("mesh.sum"):
        return jax.jit(mesh_sum)(dfm)


def dfm_scan(mesh, f_assoc: Callable, dfm):
    """Inclusive prefix scan (cross-shard prefix exchange handled by XLA)."""
    def mesh_scan(x):
        return jax.tree_util.tree_map(
            lambda v: jax.lax.associative_scan(f_assoc, v, axis=0), x)
    with span("mesh.scan"):
        return jax.jit(mesh_scan)(dfm)


def repartition(mesh, dfm):
    """Rebalance to the canonical contiguous-block partition."""
    with span("mesh.repartition"):
        return jax.tree_util.tree_map(
            lambda v: jax.device_put(v, data_sharding(mesh, v.ndim)), dfm)


def group(mesh, dest: jax.Array, dfm):
    """Move row i to bucket dest[i] (stable within bucket): sort-by-key then
    rebalance — the all-to-all exchange pattern of mpi-list.group.  One
    program, whose output lands in the contiguous-block partition."""
    def mesh_group(dest, dfm):
        order = jnp.argsort(dest, stable=True)
        return jax.tree_util.tree_map(lambda v: jnp.take(v, order, axis=0),
                                      dfm)
    with span("mesh.group"):
        out = jax.tree_util.tree_map(lambda v: data_sharding(mesh, v.ndim),
                                     dfm)
        return jax.jit(mesh_group, out_shardings=out)(dest, dfm)


def collect(dfm):
    return jax.tree_util.tree_map(
        lambda v: jax.device_get(v), dfm)
