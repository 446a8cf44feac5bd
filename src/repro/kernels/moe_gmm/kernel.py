"""Pallas TPU kernel: the gated expert MLPs of a mixture-of-experts layer
over rows sorted by expert (a grouped matmul, after MegaBlocks / megablox).

Rows `x` (N, D) come sorted by expert, `group_sizes[e]` of them for each
held expert e; rows past the groups' total belong to no expert here.  Row
r of group e becomes act(x_r W1[e]) * (x_r W3[e]) @ W2[e].  The weights
may be a stack over layers, (L, E, D, F), with the layer given as a
scalar: the kernel then reads its blocks from the stack in place, so a
layer scan hands it the whole stack and no layer's weights are copied
out first.

The row axis is cut into tiles of `tm` rows.  The grid walks "visits":
one (expert, row tile) pair for each tile an expert's rows touch, expert
by expert, so a tile shared by two experts is visited twice in a row and
its output block stays in VMEM between the two.  Scalar prefetch gives
each visit its expert and tile, and the index maps fetch that expert's
weight blocks; consecutive visits of one expert keep the same blocks, so
with the whole expert width in one block (`bf == F`) each expert with
rows is read from HBM once, and an expert with no rows is never read.
The grid has the most visits any `group_sizes` can need; the visits past
the real ones repeat the last real block indices (no new DMA) and compute
nothing.  Each visit masks the rows outside its expert's range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTS = {"silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True)}
WEIGHT_VMEM = 48 << 20      # double-buffered weight blocks, at most


def expert_block(D: int, F: int, itemsize: int) -> int:
    """The expert-width block: all of F where the three double-buffered
    weight blocks fit `WEIGHT_VMEM`, else the widest multiple of 128 that
    divides F and fits."""
    if 2 * 3 * D * F * itemsize <= WEIGHT_VMEM or F % 128:
        return F
    best = 128
    for bf in range(128, F, 128):
        if F % bf == 0 and 2 * 3 * D * bf * itemsize <= WEIGHT_VMEM:
            best = bf
    return best


def visit_plan(group_sizes, n_tiles: int, tm: int):
    """(expert, tile) of each of the n_tiles + E - 1 visits the grid holds,
    the real ones first, then the last real one repeated; and how many
    are real.  All on the device, from group_sizes (E,) int32."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(tiles)
    n_visits = visit_end[-1]
    v = jnp.minimum(jnp.arange(n_tiles + E - 1), jnp.maximum(n_visits - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(visit_end, v, side="right"), E - 1)
    tile = first[expert] + v - (visit_end - tiles)[expert]
    return (expert.astype(jnp.int32), tile.astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            n_visits.reshape(1).astype(jnp.int32))


def _gmm_kernel(layer_ref, expert_ref, tile_ref, start_ref, end_ref, nv_ref,
                x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref, *,
                tm: int, nf: int, act):
    v, f = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(0) - 1
    nv = nv_ref[0]
    e, t = expert_ref[v], tile_ref[v]
    real = v < nv
    opens = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
    closes = (v + 1 >= nv) | (tile_ref[jnp.minimum(v + 1, last)] != t)

    @pl.when(real & opens & (f == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _visit():
        x = x_ref[...]
        h1 = jnp.dot(x, w1_ref[...].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        h3 = jnp.dot(x, w3_ref[...].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        h = (act(h1) * h3).astype(x.dtype)
        y = jnp.dot(h, w2_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= start_ref[e]) & (row < end_ref[e])
        acc_ref[...] += jnp.where(mine, y, 0.0)

    @pl.when(real & closes & (f == nf - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm_pallas(x, w1, w3, w2, group_sizes, layer=None, *,
                   act: str = "silu", tm: int = 128, bf: int | None = None,
                   interpret: bool = False):
    """x (N, D) sorted by expert, N a multiple of tm; w1, w3 (E, D, F) and
    w2 (E, F, D), or stacks of them over layers with `layer` the int32
    index of the one to use; group_sizes (E,) int32 summing to at most N,
    with at least one row.  Returns (N, D) in x.dtype; rows past the
    groups' total are left unwritten (the caller masks them)."""
    if w1.ndim == 3:
        w1, w3, w2 = w1[None], w3[None], w2[None]
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                    jnp.int32), (1,))
    N, D = x.shape
    _, E, _, F = w1.shape
    assert N % tm == 0, (N, tm)
    bf = bf or expert_block(D, F, w1.dtype.itemsize)
    assert F % bf == 0, (F, bf)
    n_tiles, nf = N // tm, F // bf
    plan = visit_plan(group_sizes, n_tiles, tm)

    def rows(v, f, layer, expert, tile, start, end, nv):
        return tile[v], 0

    def f_of(v, f, nv):                 # a repeated visit keeps its blocks
        return jnp.where(v < nv[0], f, nf - 1)

    def w_in(v, f, layer, expert, tile, start, end, nv):
        return layer[0], expert[v], 0, f_of(v, f, nv)

    def w_out(v, f, layer, expert, tile, start, end, nv):
        return layer[0], expert[v], f_of(v, f, nv), 0

    item = x.dtype.itemsize
    vmem = (2 * (3 * D * bf * w1.dtype.itemsize + 2 * tm * D * item)
            + 4 * tm * (D + 3 * bf) + (8 << 20))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_tiles + E - 1, nf),
        in_specs=[pl.BlockSpec((tm, D), rows),
                  pl.BlockSpec((None, None, D, bf), w_in),
                  pl.BlockSpec((None, None, D, bf), w_in),
                  pl.BlockSpec((None, None, bf, D), w_out)],
        out_specs=pl.BlockSpec((tm, D), rows),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)])
    kernel = functools.partial(_gmm_kernel, tm=tm, nf=nf, act=ACTS[act])
    return pl.pallas_call(
        kernel,
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=interpret,
    )(layer, *plan, x, w1, w3, w2)
