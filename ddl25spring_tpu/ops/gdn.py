"""The gated delta rule's state update for one token a sequence: ``gdn_step``.

A layer keeps, for every sequence (slot) and head, a matrix ``S [dk, dv]``
(key dim x value dim, float32).  One token updates it and reads it once:

    S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

:func:`gdn_step` does that for ALL slots of a decode tick, on the pool's
whole state array ``[slots, layers, H, dk, dv]`` with the layer as a
prefetched scalar (a layer sliced out of the array would be a copy of it),
IN PLACE (``input_output_aliases``): the kernel
(``pallas_call(name="gdn_step")``, so that a trace names it) walks the LIVE
slots only (their ids are prefetched and the grid's bound is their count,
computed on the device), reads a slot's ``H`` matrices once (one block of
``H dk dv`` floats: 2 MiB at 32 x 128 x 128), applies decay, delta and the
rank-one update on the vector unit, writes them once, and emits ``o``.  A
dead slot is never visited: nothing of it is read or written, and its rows
of ``o`` come back as zeros.

The update needs ``k`` and ``q`` down the sublanes of ``S`` (one value a
key row) and ``v``, ``exp(g)``, ``beta`` along its lanes (one a value
column), so the wrapper hands the kernel two small arrays a slot: the
columns ``[2, dk, H]`` (``k``, ``q`` with the heads minor, so that a head's
column is a static lane slice) and the rows ``[3, H, dv]`` (``v``, the
decay and ``beta``, the two scalars a head spread over ``dv``).

What bounds it is bytes: ``2 x H dk dv x 4`` a live slot a layer, against
which the vectors (``(2 dk + 4 dv) H`` floats) are a hundredth.  Off the
TPU the same kernel runs in Pallas's interpret mode (the tier-1 tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 32 * 1024 * 1024  # a slot's state in and out, twice, and room


@jax.jit
def gdn_step(state, layer, q, k, v, g, beta, live):
    """One token of every live slot through one layer's state.

    ``state [S, L, H, dk, dv]``, the pool's array (float32 as served; the
    arithmetic is float32 whatever it is held in) (donate it, and
    the update is in place); ``layer`` the state layer's index; ``q, k [S,
    H, dk]`` (normalised and scaled as the model says, a key head repeated
    to its value heads), ``v [S, H, dv]``, ``g, beta [S, H]``: the token's
    own, any float type; ``live [S]`` bool.  Returns ``(o [S, H, dv]
    float32, state)``; a dead slot's state is untouched and its ``o`` 0."""
    S, L, H, dk, dv = state.shape
    f32 = jnp.float32
    if q.shape != (S, H, dk) or v.shape != (S, H, dv) or g.shape != (S, H):
        raise ValueError(
            f"state {state.shape}, q {q.shape}, v {v.shape}, g {g.shape}: "
            "want [S, L, H, dk, dv], [S, H, dk], [S, H, dv], [S, H]"
        )
    cols = jnp.stack([k.astype(f32), q.astype(f32)], axis=1)  # [S, 2, H, dk]
    cols = cols.transpose(0, 1, 3, 2)  # heads minor: a head is a lane
    spread = jnp.stack([jnp.exp(g.astype(f32)), beta.astype(f32)], axis=1)
    rows = jnp.concatenate([
        v.astype(f32)[:, None], jnp.broadcast_to(
            spread[..., None], (S, 2, H, dv)),
    ], axis=1)  # [S, 3, H, dv]
    live = live.astype(bool)
    # live slots first, in their order; what lies behind is never visited
    ids = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kernel(ids_ref, layer_ref, cols_ref, rows_ref, s_ref, o_ref, s_out):
        del ids_ref, layer_ref
        for h in range(H):
            s = s_ref[h].astype(f32)  # [dk, dv]
            k_col = cols_ref[0, :, h:h + 1]  # [dk, 1]
            q_col = cols_ref[1, :, h:h + 1]
            v_row = rows_ref[0, h:h + 1, :]  # [1, dv]
            decay = rows_ref[1, h:h + 1, :]
            b_row = rows_ref[2, h:h + 1, :]
            # S^T k of the DECAYED state: the decay is one factor a head
            kv = jnp.sum(s * k_col, axis=0, keepdims=True) * decay
            s = s * decay + k_col * (b_row * (v_row - kv))
            s_out[h] = s.astype(s_out.dtype)
            o_ref[h:h + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True)

    def slot(i, ids, layer):
        return ids[i], 0, 0, 0

    def slot_state(i, ids, layer):
        return ids[i], layer[0], 0, 0, 0

    o, state = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((S, H, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((None, 2, dk, H), slot),
                pl.BlockSpec((None, 3, H, dv), slot),
                pl.BlockSpec((None, None, H, dk, dv), slot_state),
            ],
            out_specs=(
                pl.BlockSpec((None, H, dv), lambda i, ids, layer: (ids[i], 0, 0)),
                pl.BlockSpec((None, None, H, dk, dv), slot_state),
            ),
            grid=(n_live,),
        ),
        # the state is argument 4 (behind the two prefetched scalars and
        # the two vector blocks) and result 1: updated where it lies
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=jax.default_backend() != "tpu",
        name="gdn_step",
    )(ids, layer, cols, rows, state)
    # a dead slot's rows of o were never written: whatever the buffer held
    return jnp.where(live[:, None, None], o, 0.0), state
