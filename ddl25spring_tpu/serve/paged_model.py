"""The seam between the paged server and a model it serves.

The engine (:mod:`.engine`), the speculation programs (:mod:`.spec`) and
the page pool (:mod:`.kv_pages`) know a model only through the
:class:`PagedModel` its configuration object offers:

- ``planes``: what one position of one layer leaves in the pool, as
  ``{name: trailing shape}``.  The dense block declares ``{"k": (H, hd),
  "v": (H, hd)}``; a latent-attention block ``{"ckv": (r,), "kpe":
  (d_rope,)}``.  The pool holds one array ``[n_pages + 1, layers,
  page_len, *shape]`` a plane, and no pool op learns what a plane means;
- ``plane_layers``: ``{name: how many layers hold the plane}`` for a model
  whose layers are of several kinds, of which only some leave anything in
  pages; ``None`` where every layer holds every plane.  The model's block
  indexes a plane by the layer's rank among the layers that hold it;
- ``slot_state`` and ``state_layers``: what a layer keeps a SEQUENCE and
  not a position, as ``{name: (trailing shape, dtype)}``, and how many
  layers keep it.  The pool holds one array ``[max_slots, state_layers,
  *shape]`` a name beside its planes, indexed by slot and overwritten in
  place; it has no page, no reference count and no earlier version, so
  nothing that re-enters a sequence at an earlier position can restore it
  (:func:`refuse_with_state`).  Empty for a model that keeps none;
- ``scan_units``: how many EQUAL blocks ``params["blocks"]`` stacks and
  the pass's scan walks; ``None`` is one a layer.  A model whose layer
  kinds repeat with a period makes the period its unit;
- ``layers(params, slots, rows, pages, offs, pos, live, tp_axis)``: what a
  pass shares over its layers (rotary tables, masks, weights that are not
  to be sliced by the layer scan) is taken once here; it returns
  ``run_layer(p, ui, x, cache) -> (x, cache, aux)`` (with a ``carry``:
  ``run_layer(p, ui, x, cache, c) -> (x, cache, aux, c)``), unit ``ui`` of the
  scan on ``x [B, T, D]`` at absolute positions ``pos [B, T]`` for ANY
  ``T`` (a decode tick is ``T = 1``, a prompt batch ``T = W``): write this
  pass's positions at ``(pages, layer, offs)``, gather the page view
  ``rows``, attend.  ``cache`` is ``{name: array}`` of the model's planes
  and, beside them, its slot state; ``slots [B]`` is the slot of each row
  (negative for a padding row), which only a model with slot state reads:
  a prompt pass SEATS, at ``slots``, each row's state as it stands after
  the row's last live position, and a tick updates it in place.  ``live
  [B, T]`` marks the positions that belong to a request (padding rows and
  masked positions do not).  ``aux`` is ``None``, or one small int32
  vector of the unit's counts of the pass, a layer after a layer; the
  programs append them, a row a unit, to the vector of sampled tokens, so
  that the host's one fetch brings both;
- ``carry``: what one POSITION hands from unit to unit beside ``x`` (a
  router that reads the previous layer's router state): ``carry(x)`` gives
  the first unit's, any pytree of arrays whose shapes follow ``x [B, T,
  D]``; ``run_layer`` takes it after ``cache`` and returns the next
  unit's after ``aux``.  It is of the pass alone: neither a plane (no
  later position reads it) nor slot state (no later pass does), so the
  pool never sees it, and the engine's walk over the units passes it on
  without opening it and drops it after the last.  ``None`` for a model
  whose units exchange ``x`` only, whose ``run_layer`` keeps the shorter
  form;
- ``embed(params, tokens)`` / ``unembed(params, x)``;
- ``pass_stats(aux)``: the fetched counts ``aux [n_layers, c]`` of one
  pass as two ``{name: number}``: the first are sampled, each into the ring
  ``serve.<name>``, and added to the pass's span; the second are stats of
  the span only.  ``None`` with ``aux``;
- ``prompt_pass_counts(lens, rows, width)``: what the model counts of a
  prompt pass from HOST state alone (the live rows' lengths, the pass's
  ``rows x width``), as ``{name: number}``, each sampled into the ring
  ``serve.<name>`` at the pass's dispatch and a stat of its span; ``None``
  for a model that counts nothing there;
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

from dataclasses import dataclass, field
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
    plane_layers: Mapping[str, int] | None = None
    slot_state: Mapping[str, tuple[tuple[int, ...], Any]] = field(
        default_factory=dict
    )
    state_layers: int = 0
    scan_units: int | None = None
    prompt_pass_counts: Callable | None = None
    carry: Callable | None = None

    @property
    def n_units(self) -> int:
        """Equal blocks a pass scans: ``scan_units``, else one a layer."""
        return self.n_layers if self.scan_units is None else self.scan_units

    def layers_of(self, plane: str) -> int:
        """How many layers hold ``plane``."""
        if self.plane_layers is None:
            return self.n_layers
        return self.plane_layers[plane]


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


def refuse_with_state(cfg, feature: str) -> None:
    """The one refusal of a serving feature that re-enters a sequence at an
    EARLIER position (a radix hit, a speculative rollback), splits a block
    over chips, or moves a sequence between engines, for a model that
    keeps slot state: a state is overwritten in place and the pool holds
    no earlier version of it, and the model says no way to split or ship
    it.  A model without slot state passes."""
    if paged_model(cfg).slot_state:
        raise NotImplementedError(
            f"{feature} is not offered for {type(cfg).__name__}: its paged "
            "model keeps a slot of recurrent state a sequence beside its "
            "pages, overwritten every token, which the pool can neither "
            "restore at an earlier position nor split nor hand over; "
            "PERF.md section 7 lists what each of the prefix cache, a "
            "drafter, tp_axis and the elastic hand-off would need"
        )
