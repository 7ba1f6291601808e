"""Grouped matmul over the experts a chip holds (the MoE layer's expert GEMMs).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (M, C) are
sorted by expert, ``group_sizes[g]`` rows for held expert g, and row r of
group g is multiplied by ``rhs[g]`` (C, N).  Rows past ``sum(group_sizes)``
belong to no group (assignments routed to experts held elsewhere): the
rows of the result past them that share a tile with routed rows are zero,
the whole tiles past them are never written, and a caller reads neither.
M is a static buffer (every (token, slot) assignment could land here), but
the work follows the rows actually routed:

* the grid visits (row tile, group) pairs in order, the scheme of
  ``jax.experimental.pallas.ops.tpu.megablox``: every row tile that holds
  rows of some group, once per group it holds.  A static grid of
  ``tiles_m + G - 1`` visits covers any routing; the number of active
  visits is a scalar-prefetched count;
* a visit past the active count does nothing: its block indices repeat
  the last active visit's, so it issues no DMA, no MXU work and no store;
* a row tile that two groups share is visited twice in a row, each visit
  selecting its own group's rows into the resident output block.

Three kernels, each named in its op text (``name`` and
``metadata={"kernel": ...}``) so a profile finds them: ``moe_gmm`` (the
product), ``moe_gmm_dlhs`` (its gradient for ``lhs``: the same visits
against ``rhs[g]ᵀ``) and ``moe_gmm_drhs`` (the gradient for ``rhs``: per
group, ``lhs[rows]ᵀ · grad[rows]``, accumulated over the group's visits,
empty groups visited once to be zeroed).  ``grouped_matmul`` carries the
``custom_vjp`` that ties them together, so a ``jax.vjp`` through an MoE
layer (the DFA local vjp) runs them.

Operands are float32, results float32.  On a TPU the kernels multiply in
bfloat16 and accumulate in float32: one MXU pass, which is what XLA's
default precision does for the model's other float32 matmuls.  The row
buffers are cast ahead of each call (``_mxu``), so what the vjp keeps of
the (T·top_k)-row buffers is half the size; the weights stay float32 and
are cast block by block in the kernel.  Off the TPU the kernels run in
the Pallas interpreter at float32 (tests only).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 512                       # rows of a tile
_BLOCK_BYTES = 12 * 2 ** 20    # the largest weight block a step holds
_VMEM_CAP = 100 * 2 ** 20


class Visits(NamedTuple):
    """Scalar-prefetched schedule of one call (all int32)."""

    offsets: jax.Array     # (G + 1,) first row of each group; [G] = routed rows
    group: jax.Array       # (V,) the group a visit works on
    tile: jax.Array        # (V,) the row tile it reads and writes
    active: jax.Array      # (1,) the number of visits that work


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_tile(m: int) -> int:
    """Rows of a tile for a buffer of m rows (m is padded to a multiple)."""
    return min(TM, _round_up(m, 8))


def lane_tile(n: int, c: int) -> int:
    """Output lanes of a step: the widest multiple of 128 dividing n whose
    (c, tn) float32 weight block fits the block budget; n itself where n is
    no multiple of 128 (small widths: one full block)."""
    if n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and c * tn * 4 <= _BLOCK_BYTES:
            best = tn
    return best


def visits(group_sizes, m: int, tm: int, *, visit_empty: bool) -> Visits:
    """The visit schedule for ``group_sizes`` over a buffer of m rows in
    tiles of tm (megablox's ``make_group_metadata``, one shard); visits
    past the active ones repeat the last active one's blocks."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    n_visits = tiles_m + g - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # tiles a group spans: its first row's tile through its last row's
    span = jnp.where(sizes == 0, 0, (ends + tm - 1) // tm - starts // tm)
    if visit_empty:
        span = jnp.where(sizes == 0, 1, span)
    n_active = jnp.sum(span)
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), span,
                       total_repeat_length=n_visits)
    # the k-th visit of a group reads its (first tile + k)-th tile
    first_visit = jnp.cumsum(span) - span
    vid = jnp.arange(n_visits, dtype=jnp.int32)
    tile = starts[group] // tm + (vid - first_visit[group])
    tile = jnp.minimum(tile, tiles_m - 1)
    last = jnp.maximum(n_active - 1, 0)
    active = vid < n_active
    tile = jnp.where(active, tile, tile[last]).astype(jnp.int32)
    group = jnp.where(active, group, group[last])
    return Visits(offsets, group, tile, n_active.reshape(1).astype(jnp.int32))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mxu(x):
    """An operand as the MXU takes it: bfloat16 on a TPU (one pass)."""
    return x.astype(jnp.bfloat16) if _on_tpu() and x.dtype == jnp.float32 else x


def _dot(a, b, dims):
    b = b.astype(a.dtype)  # weights stay float32 in HBM, cast per block
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _vmem_limit(*block_elems: int) -> int:
    need = 2 * 4 * sum(block_elems)
    return int(min(_VMEM_CAP, need * 1.25 + 8 * 2 ** 20))


def _pad_rows(x, mp: int):
    return x if x.shape[0] == mp else jnp.pad(x, ((0, mp - x.shape[0]), (0, 0)))


def _gmm_call(lhs, rhs, group_sizes, *, transpose_rhs: bool, name: str):
    """(M, C) × per-group (C, N) [or (N, C)ᵀ] -> (M, N); rows past the
    groups are left as the module docstring says."""
    m, c = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = row_tile(m)
    mp = _round_up(m, tm)
    lhs = _pad_rows(lhs, mp)
    tn = lane_tile(n, c)
    sched = visits(group_sizes, mp, tm, visit_empty=False)
    n_visits = mp // tm + group_sizes.shape[0] - 1
    dims = ((1,), (1,)) if transpose_rhs else ((1,), (0,))

    def kernel(offsets, group, tiles, active, lhs_ref, rhs_ref, out_ref):
        v = pl.program_id(1)
        tile = tiles[v]
        first = jnp.logical_or(v == 0, tiles[jnp.maximum(v - 1, 0)] != tile)

        @pl.when(v < active[0])
        def _():
            gid = group[v]
            rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
            mine = (rows >= offsets[gid]) & (rows < offsets[gid + 1])
            prod = _dot(lhs_ref[...], rhs_ref[...], dims)
            prev = jnp.where(first, 0.0, out_ref[...])
            out_ref[...] = jnp.where(mine, prod, prev).astype(out_ref.dtype)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, c), lambda j, v, o, g, t, a: (g[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, c, tn), lambda j, v, o, g, t, a: (g[v], 0, j))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, c), lambda j, v, o, g, t, a: (t[v], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t, a: (t[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm * c, c * tn, tm * tn)),
        interpret=not _on_tpu(),
        name=name,
        metadata={"kernel": name},
    )(*sched, lhs, rhs)
    return out[:m]


def _tgmm_call(lhs, grad, group_sizes):
    """Per group g: lhs[rows_g]ᵀ · grad[rows_g] -> (G, C, N); a group with
    no rows gets zeros."""
    m, c = lhs.shape
    n = grad.shape[1]
    n_groups = group_sizes.shape[0]
    tm = row_tile(m)
    mp = _round_up(m, tm)
    lhs, grad = _pad_rows(lhs, mp), _pad_rows(grad, mp)
    tn = lane_tile(n, c)
    sched = visits(group_sizes, mp, tm, visit_empty=True)
    n_visits = mp // tm + n_groups - 1

    def kernel(offsets, group, tiles, active, lhs_ref, grad_ref, out_ref):
        v = pl.program_id(1)
        gid = group[v]
        first = jnp.logical_or(v == 0, group[jnp.maximum(v - 1, 0)] != gid)

        @pl.when(v < active[0])
        def _():
            rows = tiles[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
            mine = (rows >= offsets[gid]) & (rows < offsets[gid + 1])
            a = jnp.where(mine, lhs_ref[...], 0.0)
            b = jnp.where(mine, grad_ref[...], 0.0)
            prod = _dot(a, b, ((0,), (0,)))
            prev = jnp.where(first, 0.0, out_ref[...])
            out_ref[...] = (prev + prod).astype(out_ref.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, c, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, c), lambda j, v, o, g, t, a: (t[v], 0)),
                pl.BlockSpec((tm, tn), lambda j, v, o, g, t, a: (t[v], j)),
            ],
            out_specs=pl.BlockSpec((None, c, tn), lambda j, v, o, g, t, a: (g[v], 0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm * c, tm * tn, c * tn)),
        interpret=not _on_tpu(),
        name="moe_gmm_drhs",
        metadata={"kernel": "moe_gmm_drhs"},
    )(*sched, lhs, grad)
    return out


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (M, C) rows sorted by group, rhs (G, C, N), group_sizes (G,)
    int32 -> (M, N) in lhs's dtype.  Rows past ``sum(group_sizes)`` are
    not results (module docstring): a caller reads only the routed rows,
    and the cotangent it gives for the others is not read either."""
    out = _gmm_call(_mxu(lhs), rhs, group_sizes, transpose_rhs=False, name="moe_gmm")
    return out.astype(lhs.dtype)


def _fwd(lhs, rhs, group_sizes):
    staged = _mxu(lhs)
    out = _gmm_call(staged, rhs, group_sizes, transpose_rhs=False, name="moe_gmm")
    # an empty array carries lhs's own dtype to the vjp
    return out.astype(lhs.dtype), (staged, rhs, group_sizes, jnp.zeros((0,), lhs.dtype))


def _bwd(res, g):
    lhs, rhs, group_sizes, like = res
    g = _mxu(g)
    d_lhs = _gmm_call(g, rhs, group_sizes, transpose_rhs=True, name="moe_gmm_dlhs")
    d_rhs = _tgmm_call(lhs, g, group_sizes)
    return d_lhs.astype(like.dtype), d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_fwd, _bwd)


@jax.jit
def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The same product as a dense matmul per group, masked: the test
    oracle."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(lhs.shape[0])[:, None]
    mask = (rows >= starts[None]) & (rows < ends[None])          # (M, G)
    per_group = jnp.einsum("mc,gcn->gmn", lhs, rhs)
    return jnp.einsum("mg,gmn->mn", mask.astype(lhs.dtype), per_group)
