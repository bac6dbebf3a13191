"""Paged KV cache: a fixed page pool + per-sequence page tables.

The dense decode cache (:func:`ddl25spring_tpu.models.decode.init_kv_cache`)
pins ``[L, B, max_len, H, hd]`` per *batch slot* for the whole run — a
sequence that finishes early keeps its full ``max_len`` slab resident
until the batch drains, which is exactly what kills continuous batching:
freed capacity never returns to the pool.  This module is the vLLM-style
alternative, TPU-first (every operation static-shaped under jit):

- **page pool**: one array ``[n_pages + 1, L, page_len, *shape]`` for
  every PLANE the served model declares (:mod:`.paged_model`: ``k`` and
  ``v`` of ``(H, hd)`` for the dense block, a latent and a rotary plane
  for a latent-attention block) — one shared arena of fixed-size pages,
  all layers of a page row together (one gather per layer serves a
  sequence's whole context).  A plane is every pool entry that is not
  one of :data:`ACCOUNTING`; no op here learns what a plane means, and
  every op that moves page contents walks all of them; the accounting
  ops (release, ref, unref, truncate) never see a plane, so the engine
  hands them :func:`accounting` alone and copies none.  The LAST row is
  a trash page: masked writes (inactive slots, padded prefill rows) land
  there instead of corrupting live pages, so no ``lax.cond`` is ever
  needed on the write path.  A plane has as many layer rows as the model
  says hold it (``PagedModel.plane_layers``; every layer, unless it says).
- **slot state**: what a model keeps a SEQUENCE and not a position
  (``PagedModel.slot_state``: a recurrent layer's state), one array
  ``[max_slots, state_layers, *shape]`` a name under the pool's one key
  :data:`SLOT_STATE`, absent for a model that declares none.  It is not
  accounting (no accounting op takes it) and not a plane: it has no page,
  no reference count and one version, the newest.  :func:`contents` hands
  a pass both, :func:`with_contents` takes both back, and no op here
  learns what a state means: a pass overwrites a slot's rows (a prompt
  pass seats them, a tick updates them), and a released slot's rows are
  dead until the next prompt pass seats that slot again.
- **page tables** ``[max_slots, pages_per_seq]`` int32 — slot s's page
  ``j`` holds its positions ``[j*page_len, (j+1)*page_len)``; ``-1``
  marks an unassigned entry.
- **allocate / append / free under jit**: batched first-fit allocation
  (argsort over the free mask; each needy slot takes the next free
  page), scatter writes at ``(page, layer, offset)``, and slot release
  that returns every page of a finished sequence to the pool in one
  scatter — continuous batching's whole point.

Equivalence contract (pinned in ``tests/test_serve.py``): attention
through the gathered page view is the SAME einsum over the SAME values
as the dense cache when ``pages_per_seq * page_len == max_len`` — pages
are gathered in table order, so position ``p`` lands at row ``p`` of the
view; dead entries are masked with the identical ``-1e30`` fill before
softmax.  In fp32 the paged decode therefore reproduces the dense
decode *bitwise*, token for token.

**Reference counting (PR 11)** makes pages *shareable*: ``refcount
[n_pages] int32`` joins the pool, ``free`` is exactly ``refcount == 0``
at all times, allocation sets a page's count to 1, and
:func:`release_slots` DECREMENTS instead of freeing — a page returns to
the free set only when its last reference drops.  Sharing enters
through two new jit-safe ops the radix prefix cache
(:mod:`ddl25spring_tpu.serve.prefix`) drives:

- :func:`adopt_prefix` — enter already-resident pages into a new
  sequence's page table by reference (``refcount += 1``; full pages of
  a cached prompt prefix are immutable after prefill, so sharing them
  is read-only), and copy-on-write duplicate the ONE partially-filled
  page a matched prefix may end in: the adopter gets a fresh first-fit
  page holding a bit-for-bit copy, so its suffix appends never touch
  the shared original.
- :func:`ref_pages` / :func:`unref_pages` — the prefix cache's own
  references (a cached page survives its owning sequence's completion;
  LRU eviction is an unref, and frees the page only at refcount 0).

**Rollback (PR 13)**: :func:`truncate_to` rolls a slot's KV frontier
back to an accepted prefix — speculative decoding's rejection path.
Table entries past the new frontier drop one reference each (the same
decrement discipline as :func:`release_slots`, so shared pages survive)
and ``seq_len`` clamps; stale values inside the kept frontier page are
overwritten before the monotone write frontier makes them readable.

The pool invariant under ANY allocate/adopt/COW/release/unref
interleaving — ``used + free == n_pages``, ``free == (refcount == 0)``,
no double-free, no leak, the COW copy reachable from exactly one page
table — is pinned by the seeded sweep in ``tests/test_serve_prefix.py``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# shared head-count validation with the dense cache layout — defined in
# models/ (the layer below) so the dependency points downward only
from ddl25spring_tpu.models.decode import resolve_heads
from ddl25spring_tpu.serve.paged_model import paged_model

Pool = dict[str, Any]

__all__ = [
    "resolve_heads", "init_page_pool", "pool_geometry", "reserve_pages",
    "write_page_ids", "write_planes", "gather_planes", "planes",
    "with_planes", "slot_state", "contents", "with_contents", "page_len_of",
    "ACCOUNTING", "SLOT_STATE", "accounting",
    "release_slots", "activate_slots", "used_pages",
    "adopt_prefix", "ref_pages", "unref_pages", "truncate_to",
]

# the pool's bookkeeping entries; every other entry is a plane, but for
# the one entry that holds the model's slot state (a dict of arrays).
# ``last_tok`` is each slot's newest sampled token, the one the next
# decode tick appends: the passes write it and read it, the host never
# uploads it
ACCOUNTING = ("page_table", "seq_len", "active", "free", "refcount",
              "last_tok")
SLOT_STATE = "slot_state"


def accounting(pool: Pool) -> Pool:
    """The bookkeeping of ``pool`` without its planes: all that
    :func:`release_slots`, :func:`ref_pages`, :func:`unref_pages` and
    :func:`truncate_to` read and write."""
    return {k: pool[k] for k in ACCOUNTING}


def planes(pool: Pool) -> Pool:
    """The page contents of ``pool``: ``{name: [n_pages + 1, L, page_len,
    ...]}``, whatever the model named them."""
    return {
        k: v for k, v in pool.items()
        if k not in ACCOUNTING and k != SLOT_STATE
    }


def with_planes(pool: Pool, new_planes: Pool, **accounting) -> Pool:
    return {**pool, **new_planes, **accounting}


def slot_state(pool: Pool) -> Pool:
    """What ``pool`` keeps a slot: ``{name: [max_slots, state_layers,
    ...]}``; empty for a model that declares none."""
    return pool.get(SLOT_STATE, {})


def contents(pool: Pool) -> Pool:
    """All that a pass of the model reads and writes of ``pool``: its
    planes and, beside them under their own names, its slot state."""
    return {**planes(pool), **slot_state(pool)}


def with_contents(pool: Pool, new: Pool, **accounting) -> Pool:
    """``pool`` with the planes and the slot state of ``new`` (as
    :func:`contents` names them) and the given accounting entries."""
    kept = slot_state(pool)
    out = {**pool, **{k: v for k, v in new.items() if k not in kept},
           **accounting}
    if kept:
        out[SLOT_STATE] = {k: new[k] for k in kept}
    return out


def page_len_of(pool: Pool) -> int:
    """Positions a page holds (a static shape fact of any plane)."""
    return next(iter(planes(pool).values())).shape[2]


def init_page_pool(
    cfg,
    *,
    n_pages: int,
    page_len: int,
    max_slots: int,
    pages_per_seq: int,
    planes: dict[str, tuple[int, ...]] | None = None,
) -> Pool:
    """Build an empty pool for the model ``cfg`` offers (or for explicit
    ``planes``: ``{name: trailing shape of one position}``, with ``cfg``
    giving ``n_layers`` and ``dtype``).  Every plane carries ``n_pages +
    1`` rows — row ``n_pages`` is the trash page masked writes target; it
    is never entered into a page table and never counted as capacity —
    and the layers that hold it; the model's slot state, where it
    declares any, stands beside them (:data:`SLOT_STATE`), zeroed."""
    if n_pages < 1 or page_len < 1 or max_slots < 1 or pages_per_seq < 1:
        raise ValueError(
            f"n_pages={n_pages}, page_len={page_len}, "
            f"max_slots={max_slots}, pages_per_seq={pages_per_seq}: "
            "every pool dimension must be >= 1"
        )
    state: dict[str, tuple] = {}
    if planes is None:
        model = paged_model(cfg)
        planes, dtype = model.planes, model.dtype
        layers_of = model.layers_of
        if model.slot_state:
            state = {
                name: ((max_slots, model.state_layers, *shape), kind)
                for name, (shape, kind) in model.slot_state.items()
            }
    else:
        dtype = cfg.dtype

        def layers_of(_name):
            return cfg.n_layers
    clash = sorted(
        (set(planes) | set(state)) & {*ACCOUNTING, SLOT_STATE}
    ) + sorted(set(planes) & set(state))
    if not planes or clash:
        raise ValueError(
            f"planes={dict(planes)}, slot state {sorted(state)}: a pool "
            "needs at least one plane, no two entries of one name, and "
            f"none named like its accounting {ACCOUNTING} or {SLOT_STATE!r}"
        )
    return {
        **{
            name: jnp.zeros(
                (n_pages + 1, layers_of(name), page_len, *shape),
                jnp.dtype(dtype),
            )
            for name, shape in planes.items()
        },
        **({SLOT_STATE: {
            name: jnp.zeros(shape, jnp.dtype(kind))
            for name, (shape, kind) in state.items()
        }} if state else {}),
        "page_table": jnp.full((max_slots, pages_per_seq), -1, jnp.int32),
        "seq_len": jnp.zeros((max_slots,), jnp.int32),
        "active": jnp.zeros((max_slots,), bool),
        "last_tok": jnp.zeros((max_slots,), jnp.int32),
        # free is kept exactly == (refcount == 0) by every mutator; the
        # redundancy buys the allocation argsort a bool mask and keeps
        # the PR-10 pool contract (`~pool["free"]` = used) intact
        "free": jnp.ones((n_pages,), bool),
        "refcount": jnp.zeros((n_pages,), jnp.int32),
    }


def pool_geometry(pool: Pool) -> dict[str, int]:
    """Static shape facts host code sizes its accounting from."""
    n_pages = int(pool["free"].shape[0])
    max_slots, pages_per_seq = (int(d) for d in pool["page_table"].shape)
    page_len = int(page_len_of(pool))
    return {
        "n_pages": n_pages,
        "page_len": page_len,
        "max_slots": max_slots,
        "pages_per_seq": pages_per_seq,
        "max_seq_len": pages_per_seq * page_len,
        # what a slot holds whatever its length: bytes of slot state
        "slot_state_bytes": sum(
            x.dtype.itemsize * int(np.prod(x.shape[1:]))
            for x in slot_state(pool).values()
        ),
    }


# --------------------------------------------------------- jit-safe ops
#
# Everything below is pure pool -> pool with static shapes, safe inside
# jit/scan/shard_map.  Masked scatters use mode="drop" with an
# out-of-bounds sentinel index instead of lax.cond — rows that must not
# write simply fall off the end.


def reserve_pages(pool: Pool, slots: jax.Array, pos: jax.Array,
                  need: jax.Array):
    """Batched first-fit allocation: every row ``i`` with ``need[i]``
    set gets the next free page, entered into ``page_table[slots[i]]``
    at the entry position ``pos[i]`` calls for (``pos // page_len`` —
    passed explicitly because prefill allocates, in ONE call over every
    (table entry, row) its pass opens, at positions its slots'
    ``seq_len`` does not reach until the prompt is fully written).

    Returns ``(pool, ok)`` — ``ok`` is False when the pool cannot cover
    the request, in which case NOTHING is allocated (admission control
    should have prevented this; the flag is the device-side backstop the
    engine surfaces as a pool-exhaustion event)."""
    free = pool["free"]
    n_pages = free.shape[0]
    P = pool["page_table"].shape[1]
    page_len = page_len_of(pool)

    need = need.astype(bool)
    # free page ids first, ascending (stable argsort over the negated
    # mask); row i's candidate page is the rank-th free one
    order = jnp.argsort(~free, stable=True)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    entry = pos // page_len
    # a needed row whose position falls past the page table fails the
    # WHOLE call: consuming its page from the free mask while the table
    # write drop-routes would leak the page forever (in no table, so
    # release_slots can never return it)
    ok = (jnp.sum(need) <= jnp.sum(free)) & jnp.all((entry < P) | ~need)
    pages = order[jnp.clip(rank, 0, n_pages - 1)]
    take = need & ok

    # a freshly-allocated page starts at refcount 1 (sole owner: the
    # allocating sequence); pages leave the free set exactly when their
    # count leaves zero
    refcount = pool["refcount"].at[
        jnp.where(take, pages, n_pages)
    ].add(1, mode="drop")
    table = pool["page_table"].at[
        jnp.where(take, slots, pool["page_table"].shape[0]),
        jnp.clip(entry, 0, P - 1),
    ].set(pages, mode="drop")
    return {
        **pool, "free": refcount == 0, "refcount": refcount,
        "page_table": table,
    }, ok


def write_page_ids(pool: Pool, slots: jax.Array, pos: jax.Array,
                   valid: jax.Array):
    """``(pages, offsets)`` for writing position ``pos`` of each slot
    (all three arguments of one shape: a vector, or ``[B, T]`` for a
    prefill pass): invalid entries (inactive slot, padded prefill row or
    position, position past the table) are routed to the trash page."""
    n_pages = pool["free"].shape[0]
    P = pool["page_table"].shape[1]
    page_len = page_len_of(pool)
    entry = pos // page_len
    rows = jnp.clip(slots, 0, pool["page_table"].shape[0] - 1)
    pages = pool["page_table"][rows, jnp.clip(entry, 0, P - 1)]
    good = valid.astype(bool) & (pages >= 0) & (entry < P)
    return jnp.where(good, pages, n_pages), pos % page_len


def write_planes(planes: Pool, layer, pages, offs, values: Pool) -> Pool:
    """Scatter one layer's ``values[name] [B, T, ...]`` into every plane
    at ``(pages[b, t], layer, offs[b, t])``.  Trash-routed positions may
    collide; the trash page is never read, so the nondeterministic
    overwrite order there is irrelevant."""
    return {
        name: plane.at[pages, layer, offs].set(values[name])
        for name, plane in planes.items()
    }


def gather_planes(planes: Pool, layer, rows) -> Pool:
    """One layer's page view of the sequences whose clamped table rows
    are ``rows [B, P]``: ``{name: [B, P * page_len, ...]}``, position
    ``p`` of a sequence at row ``p`` of its view."""
    out = {}
    for name, plane in planes.items():
        view = plane[rows, layer]  # [B, P, page_len, ...]
        out[name] = view.reshape(
            view.shape[0], view.shape[1] * view.shape[2], *view.shape[3:]
        )
    return out


def release_slots(pool: Pool, slot_mask: jax.Array) -> Pool:
    """Drop every masked slot's references and reset its table.  With
    refcounts this is a DECREMENT, not a free: a page returns to the
    free set only when its count reaches 0 — pages shared with the
    prefix cache (or with another still-live sequence) survive the
    owner's completion.  Two released slots sharing a page decrement it
    twice (scatter-add accumulates duplicates)."""
    n_pages = pool["free"].shape[0]
    rows = pool["page_table"]
    freed = slot_mask[:, None].astype(bool) & (rows >= 0)
    refcount = pool["refcount"].at[
        jnp.where(freed, jnp.clip(rows, 0, n_pages - 1), n_pages)
    ].add(-1, mode="drop")
    refcount = jnp.maximum(refcount, 0)
    table = jnp.where(slot_mask[:, None], jnp.int32(-1), rows)
    return {
        **pool,
        "free": refcount == 0,
        "refcount": refcount,
        "page_table": table,
        "seq_len": jnp.where(slot_mask, 0, pool["seq_len"]),
        "active": pool["active"] & ~slot_mask.astype(bool),
    }


def adopt_prefix(pool: Pool, slots: jax.Array, adopt_pages: jax.Array,
                 cow_src: jax.Array):
    """Enter a matched prefix into newly-admitted sequences' page
    tables (the radix cache's sharing op, run by the engine BEFORE the
    suffix prefill).  Per batch row ``b``:

    - ``adopt_pages[b, e] >= 0`` — share that resident page by
      reference at table entry ``e`` (``refcount += 1``; full prompt
      pages are immutable after their prefill, so by-reference sharing
      is read-only by construction),
    - ``cow_src[b] >= 0`` — the matched prefix ends inside this
      partially-filled page: allocate a fresh first-fit page, copy the
      source page's rows of every plane bit for bit, and seat the COPY at the
      row's next table entry (= its count of adopted entries).  The
      adopter's suffix appends land in the copy; the shared original is
      never written.  Two rows COWing the same source each get their
      own copy.

    Only pages are shared: slot state (:data:`SLOT_STATE`) has no version
    at the matched position to seat, so a model that keeps any is refused
    the prefix cache where the engine is built (``refuse_with_state``).

    ``slots[b] < 0`` marks a padding row.  Returns ``(pool, ok)`` —
    all-or-nothing like :func:`reserve_pages`: when the COW pages don't
    fit the free set, NOTHING is adopted and ``ok`` is False (the
    engine's admission accounting should have prevented it)."""
    n_pages = pool["free"].shape[0]
    P = pool["page_table"].shape[1]
    S = pool["page_table"].shape[0]

    row_ok = slots >= 0
    valid = (adopt_pages >= 0) & row_ok[:, None]
    need = (cow_src >= 0) & row_ok
    cow_entry = jnp.sum(valid, axis=1)  # first entry past the adopted run

    free = pool["free"]
    order = jnp.argsort(~free, stable=True)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    # all-or-nothing (reserve_pages discipline): a COW that cannot get
    # a fresh page, or whose entry falls past the table, fails the
    # whole call with nothing adopted
    ok = (jnp.sum(need) <= jnp.sum(free)) & jnp.all(~need | (cow_entry < P))
    fresh = order[jnp.clip(rank, 0, n_pages - 1)]
    valid = valid & ok
    take = need & ok

    refcount = pool["refcount"].at[
        jnp.where(valid, adopt_pages, n_pages)
    ].add(1, mode="drop")
    refcount = refcount.at[
        jnp.where(take, fresh, n_pages)
    ].add(1, mode="drop")

    table = pool["page_table"].at[
        jnp.where(valid, slots[:, None], S),
        jnp.broadcast_to(jnp.arange(P)[None, :], adopt_pages.shape),
    ].set(adopt_pages, mode="drop")
    table = table.at[
        jnp.where(take, slots, S),
        jnp.clip(cow_entry, 0, P - 1),
    ].set(fresh, mode="drop")

    # bit-for-bit page copy of every plane; masked rows read/write the
    # trash row
    src = jnp.where(take, cow_src, n_pages)
    dst = jnp.where(take, fresh, n_pages)
    copied = {
        name: plane.at[dst].set(plane[src], mode="drop")
        for name, plane in planes(pool).items()
    }

    return {
        **pool, **copied, "free": refcount == 0,
        "refcount": refcount, "page_table": table,
    }, ok


def truncate_to(pool: Pool, new_lens: jax.Array, mask: jax.Array,
                page_len: int | None = None) -> Pool:
    """Roll back each masked slot's KV frontier to ``new_lens[slot]``
    written positions — speculative decoding's rejection path (PR 13):
    a verify pass writes the whole draft window optimistically, then the
    first rejection truncates the sequence back to its accepted prefix.

    Per masked slot: table entries whose pages start AT or PAST the new
    frontier (``entry * page_len >= new_len``) are dropped — one
    refcount decrement each, the page returning to the free set only at
    count 0 (a shared page survives, exactly like :func:`release_slots`)
    — and ``seq_len`` clamps to ``min(seq_len, new_len)``.  The page
    holding the frontier is KEPT even when partially rolled back: its
    tail positions hold stale values, which is safe because every
    read masks ``position <= pos`` and the write frontier is monotone —
    a stale slot is overwritten (same step it next becomes readable)
    before any attention can gather it.  Masked scatters with the usual
    out-of-range sentinel: no ``lax.cond`` anywhere, jit/scan-safe.

    A ``new_len`` at or above a slot's current frontier is a no-op for
    that slot (the drafter pool rides the same call as the target pool
    with the target's rollback length; on a fully-accepted round the
    drafter has nothing to drop).

    Only the page frontier rolls back: slot state was overwritten by the
    rejected positions and cannot be restored, so a model that keeps any
    is refused a drafter where the engine is built.

    ``page_len`` is read off a plane unless the caller states it, as a
    caller that hands over the accounting arrays alone must."""
    n_pages = pool["free"].shape[0]
    P = pool["page_table"].shape[1]
    if page_len is None:
        page_len = page_len_of(pool)
    mask = mask.astype(bool)
    new_lens = jnp.maximum(new_lens, 0)

    rows = pool["page_table"]
    entry_start = (
        jnp.arange(P, dtype=jnp.int32)[None, :] * page_len
    )  # [1, P]
    drop = mask[:, None] & (entry_start >= new_lens[:, None]) & (rows >= 0)
    refcount = pool["refcount"].at[
        jnp.where(drop, jnp.clip(rows, 0, n_pages - 1), n_pages)
    ].add(-1, mode="drop")
    refcount = jnp.maximum(refcount, 0)
    table = jnp.where(drop, jnp.int32(-1), rows)
    seq_len = jnp.where(
        mask, jnp.minimum(pool["seq_len"], new_lens), pool["seq_len"]
    )
    return {
        **pool, "free": refcount == 0, "refcount": refcount,
        "page_table": table, "seq_len": seq_len,
    }


def ref_pages(pool: Pool, pages: jax.Array) -> Pool:
    """Add one reference to each listed resident page (``-1`` = pad) —
    how the prefix cache claims the prompt pages it just indexed, so
    they outlive their owning sequence."""
    n_pages = pool["free"].shape[0]
    refcount = pool["refcount"].at[
        jnp.where(pages >= 0, pages, n_pages)
    ].add(1, mode="drop")
    return {**pool, "free": refcount == 0, "refcount": refcount}


def unref_pages(pool: Pool, pages: jax.Array) -> Pool:
    """Drop one reference from each listed page (``-1`` = pad) — LRU
    eviction's device half.  A page still referenced by a live
    sequence's table survives (eviction is then only a cache miss for
    future matches, never corruption)."""
    n_pages = pool["free"].shape[0]
    refcount = pool["refcount"].at[
        jnp.where(pages >= 0, pages, n_pages)
    ].add(-1, mode="drop")
    refcount = jnp.maximum(refcount, 0)
    return {**pool, "free": refcount == 0, "refcount": refcount}


def activate_slots(pool: Pool, slots: jax.Array, valid: jax.Array) -> Pool:
    """Mark ``slots`` (rows where ``valid``) active with ``seq_len`` 0 —
    the prefill program's first act.  Assumes the engine hands out only
    released slots (their tables are already ``-1``)."""
    S = pool["seq_len"].shape[0]
    sent = jnp.where(valid.astype(bool), slots, S)
    return {
        **pool,
        "active": pool["active"].at[sent].set(True, mode="drop"),
        "seq_len": pool["seq_len"].at[sent].set(0, mode="drop"),
    }


def used_pages(pool: Pool) -> jax.Array:
    """Pages currently allocated (trash excluded) — the occupancy the
    serving telemetry tracks."""
    return jnp.sum(~pool["free"])
