"""mpi-list unit + property tests: the partition law and the monoid/functor
laws the DFM must satisfy (paper §2.3)."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.mpi_list import Context, partition_bounds


@given(st.integers(0, 500), st.integers(1, 32))
def test_partition_law(N, P):
    """Exactly the paper's rule: start = p*(N//P) + min(p, N%P); blocks are
    contiguous, ascending, and cover [0, N)."""
    spans = [partition_bounds(N, P, p) for p in range(P)]
    assert spans[0][0] == 0
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        assert e0 == s1
    assert spans[-1][1] == N
    sizes = [e - s for s, e in spans]
    assert max(sizes) - min(sizes) <= 1          # balanced


@given(st.integers(0, 200), st.integers(1, 16))
def test_iterates_collect_roundtrip(N, P):
    dfm = Context(P).iterates(N)
    dfm.check_partition_law()
    assert dfm.collect() == list(range(N))


@given(st.lists(st.integers(-100, 100), max_size=100), st.integers(1, 8))
def test_map_functor_law(xs, P):
    C = Context(P)
    f, g = (lambda x: x + 1), (lambda x: x * 2)
    a = C.scatter(xs).map(f).map(g).collect()
    b = C.scatter(xs).map(lambda x: g(f(x))).collect()
    assert a == b == [g(f(x)) for x in xs]


@given(st.lists(st.integers(-50, 50), max_size=80), st.integers(1, 8))
def test_reduce_and_scan(xs, P):
    C = Context(P)
    dfm = C.scatter(xs)
    assert dfm.reduce(lambda a, b: a + b, 0) == sum(xs)
    prefix = dfm.scan(lambda a, b: a + b, 0).collect()
    assert prefix == list(np.cumsum(xs)) if xs else prefix == []


@given(st.lists(st.integers(0, 1000), max_size=80), st.integers(1, 8),
       st.integers(1, 5))
def test_group_conserves_elements(xs, P, K):
    C = Context(P)
    g = C.scatter(xs).group(lambda x: {x % K: [x]},
                            lambda p, recs: sorted(recs))
    regrouped = sorted(sum(g.collect(), []))
    assert regrouped == sorted(xs)


@given(st.lists(st.lists(st.integers(), max_size=20), max_size=10),
       st.integers(1, 6))
def test_repartition_balances(chunks, P):
    C = Context(P)
    dfm = C.scatter(chunks)
    out = dfm.repartition(len, lambda x, n: [[e] for e in x],
                          lambda cs: [e for c in cs for e in c])
    flat = [e for blk in out.parts for x in blk for e in x]
    assert flat == [e for c in chunks for e in c]
    # per-rank record counts follow the partition law
    N = sum(len(c) for c in chunks)
    for p, blk in enumerate(out.parts):
        s, e = partition_bounds(N, P, p)
        got = sum(len(x) for x in blk)
        assert got == e - s


def test_flatmap_and_filter():
    C = Context(3)
    out = (C.iterates(10)
           .flatMap(lambda x: [x, x])
           .filter(lambda x: x % 2 == 0)
           .collect())
    assert out == [x for i in range(10) for x in (i, i) if x % 2 == 0]


def test_straggler_accounting():
    """BSP sync time = slowest minus fastest rank (the mpi-list METG)."""
    C = Context(4, jitter=lambda p: 0.01 * p)
    C.iterates(16).map(lambda x: x)
    assert C.sync_time >= 0.029


def test_mesh_bridge_single_device():
    import jax
    from repro.core.mpi_list import mesh_ops
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    dfm = mesh_ops.iterates(mesh, 32)
    out = mesh_ops.dfm_map(mesh, lambda x: x * x, dfm)
    assert int(mesh_ops.dfm_sum(mesh, out)) == sum(i * i for i in range(32))
    sc = mesh_ops.dfm_scan(mesh, lambda a, b: a + b, dfm)
    assert int(sc[-1]) == sum(range(32))
    import jax.numpy as jnp
    dest = jnp.asarray([i % 3 for i in range(32)])
    grouped = mesh_ops.group(mesh, dest, dfm)
    assert sorted(np.asarray(grouped).tolist()) == list(range(32))


# ------------------------------------------ the mesh verbs' kept programs
VERBS = ["map", "reduce", "sum", "scan", "group"]


def _square(v):
    return v * v


def _add(a, b):
    return a + b


def _times(k):
    return lambda v: v * k


@pytest.fixture
def mesh1():
    """A one-device mesh, the list 0..31 on it, and the cache emptied."""
    import jax
    from repro.core.mpi_list import mesh_ops
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    dfm = mesh_ops.iterates(mesh, 32)
    dest = jax.block_until_ready(dfm % 3)
    mesh_ops.jit_cache_clear()
    return mesh_ops, mesh, dfm, dest


@pytest.fixture
def compiles():
    """A running count of JAX's backend compiles."""
    import jax
    n = [0]

    def listen(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield lambda: n[0]
    jax.monitoring.unregister_event_duration_listener(listen)


def _verb(verb, ops, mesh, dfm, dest):
    if verb == "map":
        return ops.dfm_map(mesh, _square, dfm)
    if verb == "reduce":
        return ops.dfm_reduce(mesh, _add, dfm)
    if verb == "sum":
        return ops.dfm_sum(mesh, dfm)
    if verb == "scan":
        return ops.dfm_scan(mesh, _add, dfm)
    return ops.group(mesh, dest, dfm)


@pytest.mark.parametrize("verb", VERBS)
def test_mesh_verb_builds_its_program_once(mesh1, compiles, verb):
    ops, mesh, dfm, dest = mesh1
    first = np.asarray(_verb(verb, ops, mesh, dfm, dest))
    assert ops.jit_cache_info() == {"hits": 0, "misses": 1}
    before = compiles()
    second = np.asarray(_verb(verb, ops, mesh, dfm, dest))
    assert compiles() == before
    assert ops.jit_cache_info() == {"hits": 1, "misses": 1}
    np.testing.assert_array_equal(first, second)


def test_mesh_fresh_lambda_over_the_same_int_hits(mesh1, compiles):
    ops, mesh, dfm, _ = mesh1
    outs = [np.asarray(ops.dfm_map(mesh, _times(2), dfm))]
    before = compiles()
    for _ in range(3):
        outs.append(np.asarray(ops.dfm_map(mesh, _times(2), dfm)))
    assert compiles() == before
    assert ops.jit_cache_info() == {"hits": 3, "misses": 1}
    for out in outs:
        np.testing.assert_array_equal(out, 2 * np.arange(32))


@pytest.mark.parametrize("a, b", [(2, 3), (1, 1.0), (0.0, -0.0)])
def test_mesh_closures_over_unlike_scalars_are_kept_apart(mesh1, a, b):
    import jax.numpy as jnp
    ops, mesh, dfm, _ = mesh1
    for k in (a, b):
        out = ops.dfm_map(mesh, _times(k), dfm)
        want = dfm * k                           # JAX's own promotion
        assert out.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert (np.signbit(np.asarray(out)) ==
                np.signbit(np.asarray(want))).all()
    assert ops.jit_cache_info() == {"hits": 0, "misses": 2}
    assert ops.dfm_map(mesh, _times(1), dfm).dtype == jnp.int32
    assert ops.dfm_map(mesh, _times(1.0), dfm).dtype == jnp.float32


def test_mesh_closure_over_an_array_falls_back_to_the_function(mesh1):
    import jax.numpy as jnp
    ops, mesh, dfm, _ = mesh1
    for k in (2, 3):                             # a fresh function each
        out = ops.dfm_map(mesh, _times(jnp.int32(k)), dfm)
        np.testing.assert_array_equal(np.asarray(out), k * np.arange(32))
    assert ops.jit_cache_info() == {"hits": 0, "misses": 2}
    f = _times(jnp.int32(5))                     # one function, twice
    for _ in range(2):
        out = ops.dfm_map(mesh, f, dfm)
        np.testing.assert_array_equal(np.asarray(out), 5 * np.arange(32))
    assert ops.jit_cache_info() == {"hits": 0, "misses": 4}
    assert not ops._JITS.fns                     # built per call, not kept


def _scaled_by_kwdefault(v, *, k=3):
    return v * k


@pytest.mark.parametrize("case", ["function", "kwdefault", "partial"])
def test_mesh_unkeyable_function_is_built_per_call(mesh1, case):
    import functools
    ops, mesh, dfm, _ = mesh1
    if case == "function":
        g = _times(3)
        f = lambda v: g(v)                       # noqa: E731
    elif case == "kwdefault":
        f = _scaled_by_kwdefault
    else:
        f = functools.partial(_scaled_by_kwdefault, k=3)
    for _ in range(2):
        out = ops.dfm_map(mesh, f, dfm)
        np.testing.assert_array_equal(np.asarray(out), 3 * np.arange(32))
    assert ops.jit_cache_info() == {"hits": 0, "misses": 2}
    assert not ops._JITS.fns


def test_mesh_closure_over_an_unbound_cell_raises_and_is_not_kept(mesh1):
    ops, mesh, dfm, _ = mesh1

    def f(v):
        return v * k
    with pytest.raises(NameError):
        ops.dfm_map(mesh, f, dfm)
    assert ops.jit_cache_info() == {"hits": 0, "misses": 1}
    assert not ops._JITS.fns
    k = 3
    np.testing.assert_array_equal(np.asarray(ops.dfm_map(mesh, f, dfm)),
                                  k * np.arange(32))


def test_mesh_closure_over_an_array_is_released_after_the_call(mesh1):
    import gc
    import weakref
    import jax.numpy as jnp
    ops, mesh, dfm, _ = mesh1
    k = jnp.full((32,), 7, jnp.int32)
    gone = weakref.ref(k)
    out = ops.dfm_map(mesh, lambda v: v * k[0], dfm)
    np.testing.assert_array_equal(np.asarray(out), 7 * np.arange(32))
    del k, out
    gc.collect()
    assert gone() is None


_SCALE = 2


def _scaled_by_global(nested):
    if nested:                                   # read by nested code
        return lambda v: (lambda: v * _SCALE)()
    return lambda v: v * _SCALE


@pytest.mark.parametrize("box, nested", [("int", False), ("array", False),
                                         ("int", True)])
def test_mesh_fresh_lambda_sees_a_rebound_global(mesh1, box, nested):
    global _SCALE
    import jax.numpy as jnp
    ops, mesh, dfm, _ = mesh1
    wrap = int if box == "int" else jnp.int32
    try:
        for k in (2, 3, 2):
            _SCALE = wrap(k)
            out = ops.dfm_map(mesh, _scaled_by_global(nested), dfm)
            np.testing.assert_array_equal(np.asarray(out), k * np.arange(32))
    finally:
        _SCALE = 2
    if box == "int":                             # 2 and 3 kept, 2 again hits
        assert ops.jit_cache_info() == {"hits": 1, "misses": 2}
        assert len(ops._JITS.fns) == 2
    else:
        assert ops.jit_cache_info() == {"hits": 0, "misses": 3}
        assert not ops._JITS.fns


def test_mesh_donated_map_is_keyed_apart(mesh1):
    ops, mesh, dfm, _ = mesh1
    kept = np.asarray(ops.dfm_map(mesh, _square, dfm))
    for _ in range(2):
        given = ops.iterates(mesh, 32)
        out = ops.dfm_map(mesh, _square, given, donate=True)
        np.testing.assert_array_equal(np.asarray(out), kept)
    assert ops.jit_cache_info() == {"hits": 1, "misses": 2}


def test_mesh_jit_cache_clear_resets_the_counts(mesh1):
    ops, mesh, dfm, _ = mesh1
    for _ in range(2):
        ops.dfm_sum(mesh, dfm)
    assert ops.jit_cache_info() == {"hits": 1, "misses": 1}
    ops.jit_cache_clear()
    assert ops.jit_cache_info() == {"hits": 0, "misses": 0}
    assert int(ops.dfm_sum(mesh, dfm)) == sum(range(32))
    assert ops.jit_cache_info() == {"hits": 0, "misses": 1}


def test_mesh_jit_cache_drops_the_least_recently_used():
    from repro.core.mpi_list.mesh_ops import _JitCache
    cache = _JitCache(2)
    built = []

    def get(key):
        return cache.get(key, lambda: built.append(key) or key)
    for key in ("a", "b", "a", "c", "a", "b"):
        assert get(key) == key
    assert built == ["a", "b", "c", "b"]          # "b" went when "c" came
    assert (cache.hits, cache.misses) == (2, 4)
