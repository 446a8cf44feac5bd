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

Each verb's `jax.jit` wrapper is built once per user function and kept,
so a later call dispatches the executable the wrapper already holds: no
re-trace, no re-lowering, no compile or persistent-cache load.  The key
is the verb, what else shapes its program (`map`'s donate flag, `group`'s
mesh and each leaf's rank), and the user function as its values can
tell it apart: its `__code__`, its globals' identity, and the values of
its defaults, its closure cells and each global its code (nested code
included) reads.  A module counts by identity, an `int`, `float`,
`bool`, `str` or `None` by type and repr (`1`, `1.0` and `True` trace
apart).  So an equal closure made afresh on each call, such as
`lambda r: jnp.sum(r) % chips`, hits, and a fresh `lambda v: v * K`
misses once a global `K` is rebound.  A function the key cannot tell
apart by those values (a closure over an array or a function, a global
array, keyword-only defaults, a callable that is no plain function) is
built for its call and not kept, as every call was before.  A module's
attributes are not in the key: one rebound after the first call is not
seen.  Shapes, dtypes and input shardings stay out of the key: the kept
`jax.jit` specializes on them.  The cache keeps the 128 most recently
used wrappers, and with them the functions they close over;
`jit_cache_info()` counts its hits and misses (a wrapper built and not
kept is a miss) since the process started or since `jit_cache_clear()`.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from types import CodeType, FunctionType, ModuleType
from typing import Callable, Hashable, Iterator

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


_SCALARS = (int, float, bool, str, type(None))


def _value_key(v) -> Hashable | None:
    """A module as itself, a builtin scalar as (type, repr), else None.
    The repr and not the value: `-0.0 == 0.0`, and `nan` equals nothing;
    the type because `1`, `1.0` and `True` hash equal."""
    if type(v) is ModuleType:
        return v
    if type(v) in _SCALARS:
        return (type(v), repr(v))
    return None


def _names(code: CodeType) -> Iterator[str]:
    """The global (and attribute) names `code` and the code nested in it
    read."""
    yield from code.co_names
    for c in code.co_consts:
        if isinstance(c, CodeType):
            yield from _names(c)


def _fn_key(f: Callable) -> Hashable | None:
    """`f` as far as the program it traces to depends on it, or None where
    its values cannot say: no plain function, keyword-only defaults, or a
    closure cell, default or global read that is no module or builtin
    scalar."""
    if type(f) is not FunctionType or f.__kwdefaults__:
        return None
    try:
        cells = tuple(c.cell_contents for c in f.__closure__ or ())
    except ValueError:                          # a cell not yet bound
        return None
    g = f.__globals__
    names = tuple(sorted({n for n in _names(f.__code__) if n in g}))
    parts = tuple(tuple(map(_value_key, vs)) for vs in
                  (cells, f.__defaults__ or (), [g[n] for n in names]))
    if any(None in p for p in parts):
        return None
    return (f.__code__, id(g), names) + parts


def _key(verb: str, f: Callable, *rest) -> Hashable | None:
    """The key of `verb` called with user function `f`, or None."""
    fk = _fn_key(f)
    return None if fk is None else (verb, fk) + rest


class _JitCache:
    """The verbs' jitted wrappers by key, least recently used dropped past
    `size`, with the hits and misses since the last `clear()`.  A wrapper
    whose key is None is built for its one call and not kept."""

    def __init__(self, size: int):
        self.size = size
        self.lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self.lock:
            self.fns: OrderedDict = OrderedDict()
            self.hits = self.misses = 0

    def get(self, key: Hashable | None,
            build: Callable[[], Callable]) -> Callable:
        with self.lock:
            fn = None if key is None else self.fns.get(key)
            if fn is None:
                self.misses += 1
                fn = build()
                if key is not None:
                    self.fns[key] = fn
                    if len(self.fns) > self.size:
                        self.fns.popitem(last=False)
            else:
                self.hits += 1
                self.fns.move_to_end(key)
            return fn


_JITS = _JitCache(128)


def jit_cache_info() -> dict:
    """Hits and misses of the verbs' wrapper cache, all verbs together."""
    with _JITS.lock:
        return {"hits": _JITS.hits, "misses": _JITS.misses}


def jit_cache_clear() -> None:
    _JITS.clear()


def dfm_map(mesh, f: Callable, dfm, *, donate: bool = False):
    def build():
        def mesh_map(x):
            return jax.vmap(f)(x)
        return jax.jit(mesh_map, donate_argnums=(0,) if donate else ())
    with span("mesh.map"):
        return _JITS.get(_key("map", f, donate), build)(dfm)


def dfm_reduce(mesh, f_monoid: Callable, dfm):
    """Tree-reduction over the global list with an associative monoid
    (cross-shard combine becomes a psum-like collective via GSPMD)."""
    def build():
        def pairwise(v):
            n = v.shape[0]
            if n == 1:
                return v[0]
            if n % 2:
                return f_monoid(pairwise(v[:-1]), v[-1])
            return pairwise(f_monoid(v[0::2], v[1::2]))

        def mesh_reduce(x):
            return jax.tree_util.tree_map(pairwise, x)
        return jax.jit(mesh_reduce)
    with span("mesh.reduce"):
        return _JITS.get(_key("reduce", f_monoid), build)(dfm)


def dfm_sum(mesh, dfm):
    def build():
        def mesh_sum(x):
            return jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0), x)
        return jax.jit(mesh_sum)
    with span("mesh.sum"):
        return _JITS.get(("sum",), build)(dfm)


def dfm_scan(mesh, f_assoc: Callable, dfm):
    """Inclusive prefix scan (cross-shard prefix exchange handled by XLA)."""
    def build():
        def mesh_scan(x):
            return jax.tree_util.tree_map(
                lambda v: jax.lax.associative_scan(f_assoc, v, axis=0), x)
        return jax.jit(mesh_scan)
    with span("mesh.scan"):
        return _JITS.get(_key("scan", f_assoc), build)(dfm)


def repartition(mesh, dfm):
    """Rebalance to the canonical contiguous-block partition."""
    with span("mesh.repartition"):
        return jax.tree_util.tree_map(
            lambda v: jax.device_put(v, data_sharding(mesh, v.ndim)), dfm)


def group(mesh, dest: jax.Array, dfm):
    """Move row i to bucket dest[i] (stable within bucket): sort-by-key then
    rebalance — the all-to-all exchange pattern of mpi-list.group.  One
    program, whose output lands in the contiguous-block partition."""
    leaves, tree = jax.tree_util.tree_flatten(dfm)
    ndims = tuple(v.ndim for v in leaves)

    def build():
        def mesh_group(dest, dfm):
            order = jnp.argsort(dest, stable=True)
            return jax.tree_util.tree_map(
                lambda v: jnp.take(v, order, axis=0), dfm)
        out = tree.unflatten([data_sharding(mesh, n) for n in ndims])
        return jax.jit(mesh_group, out_shardings=out)
    with span("mesh.group"):
        return _JITS.get(("group", mesh, tree, ndims), build)(dest, dfm)


def collect(dfm):
    return jax.tree_util.tree_map(
        lambda v: jax.device_get(v), dfm)
