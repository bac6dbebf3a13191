"""The seam between the paged server and a model it serves.

The engine (:mod:`.engine`), the speculation programs (:mod:`.spec`) and
the page pool (:mod:`.kv_pages`) know a model only through the
:class:`PagedModel` its configuration object offers:

- ``planes``: what one position of one layer leaves in the pool, as
  ``{name: trailing shape}``.  The dense block declares ``{"k": (H, hd),
  "v": (H, hd)}``; a latent-attention block ``{"ckv": (r,), "kpe":
  (d_rope,)}``.  The pool holds one array ``[n_pages + 1, n_layers,
  page_len, *shape]`` a plane, and no pool op learns what a plane means;
- ``layers(params, rows, pages, offs, pos, live, tp_axis)``: what a pass
  shares over its layers (rotary tables, masks, weights that are not to
  be sliced by the layer scan) is taken once here; it returns ``run_layer(p, li, x, planes) -> (x, planes, aux)``, one block
  on ``x [B, T, D]`` at absolute positions ``pos [B, T]`` for ANY ``T``
  (a decode tick is ``T = 1``, a prompt batch ``T = W``): write this
  pass's positions at ``(pages, li, offs)``, gather the page view
  ``rows``, attend.  ``live [B, T]`` marks the positions that belong to
  a request (padding rows and masked positions do not).  ``aux`` is
  ``None``, or one small int32 vector of the layer's counts of the pass;
  the programs append them, a row a layer, to the vector of sampled
  tokens, so that the host's one fetch brings both;
- ``embed(params, tokens)`` / ``unembed(params, x)``;
- ``pass_stats(aux)``: the fetched counts ``aux [n_layers, c]`` of one
  pass as two ``{name: number}``: the first are sampled, each into the ring
  ``serve.<name>``, and added to the pass's span; the second are stats of
  the span only.  ``None`` with ``aux``;
- ``tp_shard``: ``{plane: pool axis}`` a tensor-parallel build splits over
  its model axis, or ``None`` for a model that offers no such layout;
- ``resident(params) -> params``: the parameters as the programs READ
  them.  Only the model knows which leaves its block casts at each use and
  which it multiplies as they are, so it says: the dense block's returns
  its matrices in ``cfg.dtype`` and its norm scales untouched; a model
  whose weights arrive in the served type leaves the member at its
  default, the identity.  Idempotent, and a leaf already in its type comes
  back as the same array.  The engine calls it once, before any
  placement, and holds only what it returns: no pass reads a master of
  another type or casts one.

A configuration object offers its model as ``cfg.paged_model()``;
:func:`paged_model` is the one place that asks, and holds the one error
for a model that offers none.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Mapping


def _as_given(params):
    return params


@dataclass(frozen=True)
class PagedModel:
    planes: Mapping[str, tuple[int, ...]]
    n_layers: int
    dtype: Any
    embed: Callable
    unembed: Callable
    layers: Callable
    pass_stats: Callable | None = None
    tp_shard: Mapping[str, int] | None = None
    resident: Callable = _as_given


def paged_model(cfg) -> PagedModel:
    """The model ``cfg`` offers the paged server."""
    offer = getattr(cfg, "paged_model", None)
    model = offer() if offer is not None else None
    if model is None:
        raise NotImplementedError(
            f"{type(cfg).__name__} offers no paged block for {cfg}: serve/ "
            "runs a model through the PagedModel its configuration's "
            "paged_model() returns (page planes, a block for any T, embed "
            "and unembed); the switch-MoE LLaMA (n_experts > 0) trains only"
        )
    return model
