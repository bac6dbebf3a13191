"""Flat-buffer bucketing for collective launches.

Every collective launch pays a fixed cost — an HLO op, a DMA setup, a
barrier on the slowest participant — so issuing one all-reduce /
all-gather / reduce-scatter **per pytree leaf** (how DP and ZeRO shipped
through PR 2) multiplies that cost by the leaf count.  The classic fix
(DDP gradient bucketing; "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training", arXiv:2004.13336) is to pack leaves
into a few contiguous, dtype-homogeneous buffers and run the collective
per *bucket*: O(n_buckets) launches instead of O(n_leaves), with
n_buckets set by a byte threshold.

This module is the shared planning/packing layer:

- :func:`plan_buckets` groups a pytree's leaves into dtype-homogeneous
  buckets under a byte threshold (defaults to
  :data:`DEFAULT_BUCKET_BYTES` = 4 MiB), preserving leaf order within a
  dtype.  Planning is pure metadata (shapes/dtypes only) so it works on
  tracers at trace time — callers without a params template (e.g.
  ``make_dp_train_step``) plan inside the traced function.
- :meth:`BucketPlan.pack` / :meth:`BucketPlan.unpack` move a concrete
  pytree into / out of the flat buffers (concatenate of ``reshape(-1)``;
  XLA lowers both to free bitcasts + copies that fuse with the
  collective).
- :func:`bucketed_pmean` is the drop-in for a per-leaf
  ``jax.tree.map(lambda g: lax.pmean(g, axis), grads)``: pack, pmean
  each bucket, unpack.  ``pmean``/``psum`` are elementwise across
  devices, so ``pmean(concat(xs)) == concat(pmean(xs))`` **bitwise** —
  pinned in ``tests/test_bucketing.py``.

ZeRO's row-packed ``[n, k]`` layout buckets with the same plan by
overriding the per-leaf packed size (``sizes=`` = the padded row length
``k``); the gather/scatter plumbing specific to that layout lives in
:mod:`ddl25spring_tpu.parallel.zero`.

**Overlapped mode (PR 8).**  Post-hoc bucketing still reduces *after*
``value_and_grad`` returns, i.e. the collectives sit textually after
the whole backward, and — worse — flatten-order buckets mix early- and
late-layer leaves, so a bucket's collective cannot start until its
*earliest* layer's cotangent exists, which is the very END of the
backward pass.  :func:`overlap_wrap` restructures both facts away:
params pass through one identity ``custom_vjp`` per bucket *inside the
differentiated function*, whose bwd rule packs that bucket's cotangents
and issues the reduction (``pmean``/``psum``/``psum_scatter``) the
moment they exist; buckets are planned in **backward-readiness order**
(``order="backward"``: the last layers' leaves fill bucket 0), so
bucket k's collective depends only on layers >= k and can run while
layer k-1's backward computes — the compute/comms overlap schedule of
arXiv:2204.06514 §4.2, expressed as dataflow XLA's latency-hiding
scheduler can exploit.  Reduced grads come straight out of
``jax.value_and_grad`` — bitwise-equal to the post-hoc path (psum is
elementwise; packing commutes with it), pinned in
``tests/test_bucketing.py``.

The bucket threshold itself is tunable per host: builders default to
:data:`AUTO`, resolved at BUILD time by :func:`resolve_bucket_bytes`
from the ``DDL25_BUCKET_BYTES`` env knob (via the sanctioned
``utils.config`` boundary — rule S101), so a size found on the chip
applies without touching code.  ``describe()`` hooks pin
explicit sizes so compile-time signatures never drift with the
environment.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

# builders' bucket_bytes default: resolve DDL25_BUCKET_BYTES at build
# time (resolve_bucket_bytes); a string sentinel so `None` keeps meaning
# "per-leaf, no bucketing" as it has since PR 3
AUTO = "auto"


def default_bucket_bytes() -> int | None:
    """The effective bucket threshold when a builder is handed
    :data:`AUTO`: ``DDL25_BUCKET_BYTES`` (bytes; ``0`` restores the
    per-leaf path) or :data:`DEFAULT_BUCKET_BYTES` when unset.  Like
    :func:`donation_default`, the env read routes through
    :func:`~ddl25spring_tpu.utils.config.env_int` — the one sanctioned
    env boundary (rule S101) — and is resolved when the step is BUILT,
    never at trace time."""
    from ddl25spring_tpu.utils.config import env_int

    bb = env_int("DDL25_BUCKET_BYTES", DEFAULT_BUCKET_BYTES)
    return bb if bb > 0 else None


def resolve_bucket_bytes(bucket_bytes) -> int | None:
    """Normalize a builder's ``bucket_bytes`` kwarg: :data:`AUTO` ->
    :func:`default_bucket_bytes` (the env knob), ``None``/``0`` -> None
    (per-leaf), anything else -> ``int(bucket_bytes)``."""
    if bucket_bytes == AUTO:
        return default_bucket_bytes()
    if not bucket_bytes:
        return None
    return int(bucket_bytes)


def donation_default() -> bool:
    """Resolve the ``donate=None`` default of every train-step builder.

    Buffer donation is ON by default (``donate_argnums=(0, 1)`` aliases
    the params/opt-state inputs to the matching outputs, halving their
    HBM residency) and opt-out via ``DDL25_DONATE=0`` — the test suite's
    ``conftest.py`` sets that, because the equivalence-oracle tests
    re-use one input tree across several steps, which donation
    (correctly) invalidates.  Donation-specific tests and every
    ``describe()`` compile-analytics hook pass ``donate=True``
    explicitly, so the pinned programs are the donated ones.

    The env read itself lives in :func:`~ddl25spring_tpu.utils.config.env_flag`
    — the one sanctioned env boundary — so this module (which builds
    traced computations) carries no ``os.environ`` dependency of its own
    (``graft_lint`` rule S101).
    """
    from ddl25spring_tpu.utils.config import env_flag

    return env_flag("DDL25_DONATE", default=True)


def donate_argnums(donate: bool | None) -> tuple[int, ...]:
    """The ``jax.jit(donate_argnums=...)`` value every train-step builder
    uses: alias the params (arg 0) and optimizer state (arg 1) inputs to
    the matching outputs, so the updated trees reuse the old trees'
    buffers instead of double-residing in HBM for the step's duration.
    RNG keys are not donated — no output aliases them, so donating the
    8-byte buffer would only buy an unusable-donation warning.

    ``donate=None`` resolves via :func:`donation_default`."""
    if donate is None:
        donate = donation_default()
    return (0, 1) if donate else ()


@dataclass(frozen=True)
class BucketPlan:
    """Grouping of a pytree's leaves into dtype-homogeneous flat buckets.

    ``buckets[b]`` lists leaf indices (flatten order); ``sizes[i]`` is
    the element count leaf ``i`` contributes to its bucket (== the leaf
    size for plain packing; == the padded row length ``k`` for ZeRO's
    ``[n, k]`` layout).  Frozen + hashable-free: built fresh at trace
    time, never cached across traces.
    """

    treedef: jax.tree_util.PyTreeDef
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple
    sizes: tuple[int, ...]
    buckets: tuple[tuple[int, ...], ...]

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_leaves(self) -> int:
        return len(self.sizes)

    def bucket_dtype(self, b: int):
        return self.dtypes[self.buckets[b][0]]

    def bucket_size(self, b: int) -> int:
        """Total elements in bucket ``b``."""
        return sum(self.sizes[i] for i in self.buckets[b])

    def offsets(self, b: int) -> list[int]:
        """Element offset of each slot within bucket ``b``'s buffer."""
        offs, acc = [], 0
        for i in self.buckets[b]:
            offs.append(acc)
            acc += self.sizes[i]
        return offs

    def pack(self, tree) -> list[jax.Array]:
        """Pytree -> one 1-D buffer per bucket (leaves flattened in
        bucket order).  Leaf ``i`` must hold exactly ``sizes[i]``
        elements."""
        leaves = self.treedef.flatten_up_to(tree)
        bufs = []
        for idxs in self.buckets:
            parts = [leaves[i].reshape(-1) for i in idxs]
            bufs.append(parts[0] if len(parts) == 1
                        else jnp.concatenate(parts))
        return bufs

    def unpack(self, bufs) -> object:
        """Inverse of :meth:`pack`: buffers -> pytree with the plan's
        leaf shapes/dtypes."""
        leaves: list = [None] * self.n_leaves
        for b, idxs in enumerate(self.buckets):
            off = 0
            for i in idxs:
                leaves[i] = (
                    bufs[b][off:off + self.sizes[i]]
                    .reshape(self.shapes[i])
                    .astype(self.dtypes[i])
                )
                off += self.sizes[i]
        return self.treedef.unflatten(leaves)


def plan_buckets(
    tree,
    bucket_bytes: int | float = DEFAULT_BUCKET_BYTES,
    sizes: list[int] | None = None,
    order: str = "forward",
) -> BucketPlan:
    """Greedy order-preserving packing: walk the leaves in flatten order,
    appending each to the open bucket of its dtype until adding it would
    exceed ``bucket_bytes``, then seal and open a new one.  Every leaf
    lands somewhere (a single leaf above the threshold gets a bucket of
    its own), and buckets never mix dtypes — a bf16 grad concatenated
    into an fp32 buffer would silently upcast the wire bytes.

    ``sizes`` overrides the per-leaf packed element count (ZeRO's padded
    ``k`` rows); default is the leaf's own size.  Only shapes/dtypes are
    read, so ``tree`` may hold tracers.

    ``order="backward"`` walks the leaves in REVERSED flatten order —
    the bucket composition the overlapped gradient path needs: flatten
    order tracks the forward pass, so cotangents arrive in reverse, and
    a bucket must wait for its *earliest* member.  Reverse-walked
    buckets group leaves that become ready together in the backward
    (bucket 0 = the last layers, complete first), instead of forward
    buckets whose first leaf is the last cotangent of the whole pass.
    Pack/unpack are index-driven, so both orders round-trip identically.
    """
    import numpy as np

    if order not in ("forward", "backward"):
        raise ValueError(f"order must be 'forward' or 'backward', got {order!r}")
    leaves, treedef = jax.tree.flatten(tree)
    # getattr-first so abstract templates (jax.ShapeDtypeStruct from
    # eval_shape) plan identically to concrete arrays
    shapes = tuple(
        tuple(l.shape) if hasattr(l, "shape") else tuple(jnp.shape(l))
        for l in leaves
    )
    dtypes = tuple(jnp.result_type(l) for l in leaves)
    if sizes is None:
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    if len(sizes) != len(leaves):
        raise ValueError(
            f"sizes has {len(sizes)} entries for {len(leaves)} leaves"
        )
    bucket_bytes = max(int(bucket_bytes), 1)
    walk = (
        list(enumerate(zip(dtypes, sizes)))
        if order == "forward"
        else list(enumerate(zip(dtypes, sizes)))[::-1]
    )
    open_by_dtype: dict = {}  # dtype -> (indices, bytes)
    buckets: list[tuple[int, ...]] = []
    seen_order: list = []  # dtype keys in first-seen order, for determinism
    for i, (dt, sz) in walk:
        nbytes = sz * dt.itemsize
        cur = open_by_dtype.get(dt)
        if cur is None:
            open_by_dtype[dt] = ([i], nbytes)
            seen_order.append(dt)
            continue
        idxs, used = cur
        if used + nbytes > bucket_bytes and idxs:
            buckets.append(tuple(idxs))
            open_by_dtype[dt] = ([i], nbytes)
        else:
            idxs.append(i)
            open_by_dtype[dt] = (idxs, used + nbytes)
    for dt in seen_order:
        idxs, _ = open_by_dtype[dt]
        if idxs:
            buckets.append(tuple(idxs))
    return BucketPlan(
        treedef=treedef,
        shapes=shapes,
        dtypes=dtypes,
        sizes=tuple(int(s) for s in sizes),
        buckets=tuple(buckets),
    )


def n_buckets_for(tree, bucket_bytes: int | float = DEFAULT_BUCKET_BYTES,
                  sizes: list[int] | None = None) -> int:
    """Bucket count the plan would produce (for describe() metadata and
    the compile-report ``n_buckets`` column)."""
    return plan_buckets(tree, bucket_bytes, sizes).n_buckets


def bucketed_pmean(tree, axis: str,
                   bucket_bytes: int | float = DEFAULT_BUCKET_BYTES):
    """``lax.pmean`` over ``axis`` of every leaf, launched per bucket
    instead of per leaf.  Bitwise-equal to the per-leaf tree-map (psum is
    elementwise across devices; concatenation commutes with it)."""
    plan = plan_buckets(tree, bucket_bytes)
    return plan.unpack([lax.pmean(b, axis) for b in plan.pack(tree)])


def bucketed_psum(tree, axis: str,
                  bucket_bytes: int | float = DEFAULT_BUCKET_BYTES):
    """Per-bucket ``lax.psum`` of every leaf (see :func:`bucketed_pmean`)."""
    plan = plan_buckets(tree, bucket_bytes)
    return plan.unpack([lax.psum(b, axis) for b in plan.pack(tree)])


# ---------------------------------------------------- overlapped backward


def _vary(x, axes):
    """``x`` typed varying over ``axes`` — cast only over those it is not
    varying over yet (``lax.pcast`` of an already-varying value raises)."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def overlap_wrap(tree, plan: BucketPlan, reduce_bucket, vary_axes=()):
    """Route ``tree`` through one identity ``custom_vjp`` per bucket so
    each bucket's gradient reduction is issued INSIDE the backward, at
    the dataflow point where that bucket's cotangents are complete.

    Must be applied to the params *inside the differentiated function*
    — wrapping outside ``jax.grad``'s scope means the bwd rules never
    run and the grads come back unreduced.  The forward is identity
    (zero HLO once XLA folds it); the backward of bucket ``b`` receives
    the bucket's cotangent leaves and returns ``reduce_bucket(cts, b)``
    — a tuple of reduced cotangents in the same shapes.  With buckets
    planned ``order="backward"`` the k-th wrapper's bwd fires while
    layer k-1's backward still computes, so its collective is
    schedulable concurrently with the remaining backward — the overlap
    the sync post-hoc path (:func:`bucketed_pmean` after
    ``value_and_grad``) structurally forfeits when buckets span distant
    layers.

    Typing: the leaves must reach the loss device-varying, or autodiff
    psums each leaf's cotangent itself before the bwd rule sees it.
    ZeRO hands in params it has already cast; DP hands in its INVARIANT
    params and names ``vary_axes`` — the forward casts them there, under
    the rule, so the only transpose is ``reduce_bucket`` and a ``pmean``
    there leaves the grads invariant, as DP's ``out_specs=P()`` needs.
    Either way the bwd rule re-types what ``reduce_bucket`` returns to
    the primal's own type (a custom_vjp must; see
    :func:`_bucket_barrier`).

    ``reduce_bucket(cts: tuple, b: int) -> tuple`` owns the collective:
    :func:`flat_bucket_reduce` builds the flat-concat ``pmean``/``psum``
    closure DP and ZeRO-1 use; ZeRO-2's row-scatter closure lives in
    :mod:`ddl25spring_tpu.parallel.zero`.
    """
    leaves = plan.treedef.flatten_up_to(tree)
    out = list(leaves)
    for b, idxs in enumerate(plan.buckets):
        group = tuple(leaves[i] for i in idxs)
        barrier = _bucket_barrier(
            reduce_bucket, b, tuple(vary_axes),
            [jax.typeof(g).vma for g in group],
        )
        for i, o in zip(idxs, barrier(group)):
            out[i] = o
    return plan.treedef.unflatten(out)


def _bucket_barrier(reduce_bucket, b: int, vary_axes: tuple, primal_vma):
    """One bucket's identity-forward / reduce-backward ``custom_vjp``
    (a factory so the loop in :func:`overlap_wrap` closes over the
    right bucket index).  ``primal_vma`` is each input leaf's set of
    varying axes: the cotangents the rule returns must carry exactly
    that type, so a reduction that made them invariant (``pmean`` of a
    varying primal's cotangent) is cast back — the collective stays
    where the bwd issued it, only its result's type changes."""

    @jax.custom_vjp
    def barrier(group: tuple):
        return tuple(_vary(g, vary_axes) for g in group)

    def fwd(group):
        return barrier(group), None

    def bwd(_, cts):
        reduced = reduce_bucket(tuple(cts), b)
        return (tuple(_vary(r, v) for r, v in zip(reduced, primal_vma)),)

    barrier.defvjp(fwd, bwd)
    return barrier


def flat_bucket_reduce(plan: BucketPlan, axis, op: str = "pmean"):
    """The flat-concat bucket reducer for :func:`overlap_wrap`: pack the
    bucket's cotangents into one 1-D buffer, ``pmean``/``psum`` it over
    ``axis``, split back.  One collective per bucket, issued in the
    backward — the same arithmetic per element as :func:`bucketed_pmean`
    (psum is elementwise; concatenation commutes with it), so the
    overlapped gradient path is bitwise-equal to the post-hoc one."""
    if op not in ("pmean", "psum"):
        raise ValueError(f"op must be 'pmean' or 'psum', got {op!r}")
    reduce = lax.pmean if op == "pmean" else lax.psum

    def reduce_bucket(cts, b):
        idxs = plan.buckets[b]
        buf = (
            cts[0].reshape(-1) if len(cts) == 1
            else jnp.concatenate([c.reshape(-1) for c in cts])
        )
        buf = reduce(buf, axis)
        out, off = [], 0
        for i in idxs:
            size = plan.sizes[i]
            out.append(
                buf[off:off + size]
                .reshape(plan.shapes[i])
                .astype(plan.dtypes[i])
            )
            off += size
        return tuple(out)

    return reduce_bucket


def overlapped_grad_reduce(tree, axis, bucket_bytes, op: str = "pmean"):
    """Convenience wrapper: plan ``tree``'s leaves into backward-
    readiness buckets and :func:`overlap_wrap` them with the flat
    ``pmean``/``psum`` reducer.  Apply to the (axis-invariant) params
    inside the differentiated function; ``jax.value_and_grad`` then
    returns already-reduced, invariant grads, with one collective per
    bucket embedded in the backward dataflow."""
    plan = plan_buckets(tree, bucket_bytes, order="backward")
    axes = axis if isinstance(axis, tuple) else (axis,)
    return overlap_wrap(
        tree, plan, flat_bucket_reduce(plan, axis, op), vary_axes=axes
    )
