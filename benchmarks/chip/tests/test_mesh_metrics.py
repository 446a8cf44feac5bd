"""The reader of the mesh verbs' kept-program counter: what
`jit_hit_share.mesh` counts, and when it reads nothing."""
from types import SimpleNamespace

import pytest

import registry

HIT_SHARE = registry.metric("jit_hit_share.mesh")


def _ctx(ops):
    return SimpleNamespace(trace=None, work=SimpleNamespace(ops=ops))


def _ops(hits, misses):
    return SimpleNamespace(
        jit_cache_info=lambda: {"hits": hits, "misses": misses})


def test_hit_share_is_hits_over_calls():
    # 5 misses in set-up, then 5 hits a step for 99 steps
    assert HIT_SHARE.read(_ctx(_ops(495, 5))) == pytest.approx(99.0)
    assert HIT_SHARE.read(_ctx(_ops(0, 5))) == 0.0


def test_hit_share_reads_the_program_itself():
    import jax

    from repro.core.mpi_list import mesh_ops

    mesh = jax.make_mesh((4,), ("data",))
    x = mesh_ops.iterates(mesh, 32)
    mesh_ops.jit_cache_clear()
    assert HIT_SHARE.read(_ctx(mesh_ops)) is None     # no call yet
    for _ in range(4):
        mesh_ops.dfm_sum(mesh, x)
    assert HIT_SHARE.read(_ctx(mesh_ops)) == 75.0


@pytest.mark.parametrize("ops", [SimpleNamespace(), None])
def test_a_program_without_the_counter_reads_nothing(ops):
    assert HIT_SHARE.read(_ctx(ops)) is None
