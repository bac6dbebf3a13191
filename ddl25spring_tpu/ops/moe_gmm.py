"""Grouped matrix product for routed experts: ``moe_gmm``.

``out[r] = lhs[r] @ rhs[layer, g]`` for every row ``r`` of group ``g``,
where the rows of ``lhs [m, k]`` are sorted by group and ``group_sizes [G]``
says how many each group has.  The sizes are DATA: one compiled program
serves any routing, the work grows with the rows that belong to a group
(``sum(group_sizes)`` may be less than ``m``; what lies behind is never
visited and comes back as zeros), and an empty group costs nothing, so
that its weights are not read.  There is no capacity and nothing is
dropped: one group may take every row.

The kernel (``pallas_call(name="moe_gmm")``, so that a trace names it) is
adapted from ``jax.experimental.pallas.ops.tpu.megablox.gmm``: the grid is
``(n tiles, row-tile visits, k tiles)``; a row tile is visited once for
every group that has rows in it, with a mask on the store, and the number
of visits is computed on the device from the sizes (a dynamic grid bound).
What differs: ``rhs`` is the WHOLE stack ``[L, G, k, n]`` of a model's
layers with the layer as a prefetched scalar, because a layer sliced out
of the stack by a scan would be copied for a custom call (1.6 GB a layer
at the served widths); no sharded-group offset, no transposed ``rhs``, no
accumulation into an existing output.

Off the TPU the same kernel runs in Pallas's interpret mode (the tier-1
tests); on it, weights stream ``tk x tn`` blocks through VMEM once a
visit, so a tick's few rows an expert are bound by the held experts'
bytes and a prompt batch's hundred rows an expert by the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows a visit multiplies (one MXU pass on a v5e is 128 wide; at 128 a
# visit's flops stay under the time its weight block takes from HBM, so a
# tick stays bound by bytes), and the weight block a step streams
TILE_M = 128
TILE_K = 2048
TILE_N = 1024
VMEM_LIMIT = 48 * 1024 * 1024  # two 4 MiB weight blocks in flight, and room


def _tile(dim: int, want: int) -> int:
    """``want`` where it divides ``dim``, else the whole dimension (the
    small sizes of the tests)."""
    return want if dim % want == 0 else dim


def group_metadata(group_sizes, m: int, tm: int):
    """Which group and which row tile each visit of the grid works on.

    Returns ``(group_offsets [G + 1], group_ids [V], m_tile_ids [V]),
    num_visits`` with ``V = m / tm + G - 1`` the most visits any sizes
    need: a tile is visited by the group that owns its first row and once
    more for every other non-empty group that starts inside it.  Tiles
    behind ``sum(group_sizes)`` belong to no group and fall behind
    ``num_visits``."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0, rounded // tm)
    V = tiles_m + G - 1
    group_ids = jnp.repeat(
        jnp.arange(G, dtype=jnp.int32), group_tiles, total_repeat_length=V
    )
    # a group that starts inside a tile (not on its first row) visits it
    # besides its owner; empty groups visit nothing
    inside = (starts % tm != 0) & (group_sizes > 0)
    extra = jnp.zeros(tiles_m + 1, jnp.int32).at[
        jnp.where(inside, starts // tm, tiles_m)
    ].add(1)[:tiles_m]
    m_tile_ids = jnp.repeat(
        jnp.arange(tiles_m, dtype=jnp.int32), extra + 1, total_repeat_length=V
    )
    return (offsets, group_ids, m_tile_ids), group_tiles.sum()


@jax.jit
def moe_gmm(lhs, rhs, group_sizes, layer=0):
    """``lhs [m, k]`` (rows sorted by group) times ``rhs [L, G, k, n]`` at
    ``layer`` under ``group_sizes [G]`` int32 -> ``[m, n]`` in ``lhs``'s
    type; rows behind ``sum(group_sizes)`` are zeros."""
    out_dtype = lhs.dtype
    m0, k = lhs.shape
    L, G, k2, n = rhs.shape
    if k2 != k or group_sizes.shape != (G,):
        raise ValueError(
            f"lhs {lhs.shape}, rhs {rhs.shape}, group_sizes "
            f"{group_sizes.shape}: want [m, k], [L, G, k, n], [G]"
        )
    group_sizes = group_sizes.astype(jnp.int32)
    tm = TILE_M if m0 >= TILE_M else -(-m0 // 8) * 8
    m = -(-m0 // tm) * tm
    if m != m0:
        lhs = jnp.pad(lhs, ((0, m - m0), (0, 0)))
    tk, tn = _tile(k, TILE_K), _tile(n, TILE_N)
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_visits = group_metadata(group_sizes, m, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kernel(offsets, group_ids, m_tile_ids, layer_ref, lhs_ref, rhs_ref,
               out_ref, acc):
        del layer_ref
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k_i == tiles_k - 1)
        def _():
            # only this group's rows of the tile: another group's visit
            # of the same tile wrote, or will write, the others
            g = group_ids[visit]
            row = (lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                   + m_tile_ids[visit] * tm)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(
                mine, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_dtype)

    def lhs_index(n_i, visit, k_i, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[visit], k_i

    def rhs_index(n_i, visit, k_i, offsets, group_ids, m_tile_ids, layer):
        return layer[0], group_ids[visit], k_i, n_i

    def out_index(n_i, visit, k_i, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[visit], n_i

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((None, None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=jax.default_backend() != "tpu",
        name="moe_gmm",
    )(*meta, layer, lhs, rhs)
    # rows of no group were never written: whatever the buffer held
    in_group = jnp.arange(m)[:, None] < meta[0][-1]
    return jnp.where(in_group, out, jnp.zeros((), out_dtype))[:m0]
