"""The pool's accounting ops run on the accounting arrays alone.

``release``, ``ref``, ``unref`` and ``truncate`` read and write only
``kv_pages.ACCOUNTING``; the engine hands their programs those six arrays
(``ServeEngine._account``) and merges the result back on the host, so

- no plane is a parameter or a result of the program that is dispatched,
- every plane of ``eng.pool`` / ``eng.draft_pool`` is the SAME buffer after
  the op as before it (the parent copied each plane whole: an un-donated
  jit may not alias an output to an input),
- the accounting arrays are bit for bit what the eager pool -> pool function
  of ``kv_pages`` computes on the whole pool, at every dispatch of a seeded
  interleaving of admit / evict / release / truncate,

over the dense planes (``k``, ``v``), the dense planes split over two
devices, two pools under speculation, and the latent planes.  ``adopt`` is
the one sharing op that WRITES planes: it keeps the whole pool and donates
it where the engine's programs donate theirs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import engine as engine_mod, kv_pages
from ddl25spring_tpu.serve.engine import ServeEngine

from test_mistral4 import FAMILY, tiny_config
from test_serve_prefix import CFG, assert_pool_invariants, drain

PAGE = 4
OPS = ("release", "ref", "unref", "truncate")
# the eager pool -> pool function each program is held to
EAGER = {
    engine_mod._release: kv_pages.release_slots,
    engine_mod._ref: kv_pages.ref_pages,
    engine_mod._unref: kv_pages.unref_pages,
    engine_mod._truncate: kv_pages.truncate_to,
}
# engines by the planes their pools hold (and how): keywords of ServeEngine
VARIANTS = {
    "dense": dict(family="dense"),
    "dense-tp2": dict(family="dense", tp=2),
    "dense-spec": dict(family="dense", spec_k=2, draft_layers=1),
    "latent": dict(family="latent"),
}


@pytest.fixture(scope="module")
def models():
    """``{family: (cfg, params)}``: the tiny dense block (planes ``k``,
    ``v``) and the published latent-attention configuration with every
    width shrunk (planes ``ckv``, ``kpe``), two layers deep."""
    latent = FAMILY.build(tiny_config(layers=2))
    return {
        "dense": (CFG, llama.init_llama_params(jax.random.PRNGKey(0), CFG)),
        "latent": (latent, FAMILY.init_params(latent, 3)),
    }


def make_engine(models, variant, **kw):
    kw = {**VARIANTS[variant], **kw}
    cfg, params = models[kw.pop("family")]
    kw.setdefault("page_len", PAGE)
    kw.setdefault("n_pages", 8)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_batch", 2)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("clock", "virtual")
    kw.setdefault("prefix_cache", True)
    return ServeEngine(params, cfg, **kw)


def pools_of(eng):
    return {"pool": eng.pool} | (
        {"draft_pool": eng.draft_pool} if eng.spec_k else {}
    )


def plane_buffers(pool):
    """Where each plane of ``pool`` lives: a pointer a device shard."""
    return {
        name: [s.data.unsafe_buffer_pointer() for s in x.addressable_shards]
        for name, x in kv_pages.planes(pool).items()
    }


def plane_shapes(eng):
    return {
        x.shape for pool in pools_of(eng).values()
        for x in kv_pages.planes(pool).values()
    }


def dispatch_of(eng, op):
    """``(program, args, static)`` of ``op`` at the shapes the engine
    dispatches it with; every argument a no-op (padding ids, empty masks)."""
    S = eng.max_slots
    return {
        "release": (eng._release, (jnp.zeros((S,), bool),), {}),
        "ref": (engine_mod._ref, (jnp.full(
            (eng.pages_per_seq * eng.prefill_batch,), -1, jnp.int32),), {}),
        "unref": (engine_mod._unref,
                  (jnp.full((eng.n_pages,), -1, jnp.int32),), {}),
        "truncate": (engine_mod._truncate,
                     (jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool)),
                     {"page_len": eng.page_len}),
    }[op]


class Spy:
    """A program that remembers what it was called with."""

    def __init__(self, program):
        self.program, self.calls = program, []

    def __call__(self, *args, **static):
        self.calls.append((args, static))
        return self.program(*args, **static)


# ------------------------------- (a) the program takes and returns no plane


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_the_dispatched_program_has_no_plane_among_its_avals(
        models, variant, op):
    eng = make_engine(models, variant)
    program, args, static = dispatch_of(eng, op)
    for pool in pools_of(eng).values():
        spy = Spy(program)
        eng._account(spy, pool, *args, **static)
        ((seen, seen_static),) = spy.calls
        lowered = program.lower(*seen, **seen_static)
        ins = {a.shape for a in jax.tree.leaves(lowered.in_avals)}
        outs = {o.shape for o in jax.tree.leaves(lowered.out_info)}
        assert not (ins | outs) & plane_shapes(eng), (ins, outs)
        assert set(seen[0]) == set(kv_pages.ACCOUNTING)
        assert set(lowered.out_info) == set(kv_pages.ACCOUNTING)
        # six accounting arrays and the op's own arguments, nothing else
        assert len(jax.tree.leaves(lowered.in_avals)) == 6 + len(args)


# --------------------------------- (b) the planes are the buffers they were


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_every_plane_is_the_same_buffer_after_the_op(models, variant, op):
    eng = make_engine(models, variant)
    program, args, static = dispatch_of(eng, op)
    for name, pool in pools_of(eng).items():
        before = plane_buffers(pool)
        assert before and all(before.values())
        after = eng._account(program, pool, *args, **static)
        assert plane_buffers(after) == before, name
        assert set(after) == set(pool)
        for plane in before:
            assert after[plane] is pool[plane]


def test_the_parents_whole_pool_form_copied_every_plane(models):
    """What this file guards against, shown once: the same program over the
    whole pool returns each plane in another buffer."""
    eng = make_engine(models, "dense")
    program, args, _ = dispatch_of(eng, "ref")
    copied = plane_buffers(program(eng.pool, *args))
    assert all(copied[p] != b for p, b in plane_buffers(eng.pool).items())


def test_truncate_reads_page_len_off_a_plane_unless_told(models):
    """``truncate_to`` keeps its pool -> pool form on a whole pool; on the
    accounting alone it needs the page length stated."""
    pool = make_engine(models, "dense").pool
    lens, mask = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    whole = kv_pages.truncate_to(pool, lens, mask)
    assert set(whole) == set(pool)
    part = kv_pages.truncate_to(kv_pages.accounting(pool), lens, mask,
                                page_len=PAGE)
    assert set(part) == set(kv_pages.ACCOUNTING)
    with pytest.raises(StopIteration):
        kv_pages.truncate_to(kv_pages.accounting(pool), lens, mask)


# ---------- (c) bit for bit the eager whole-pool function, at every dispatch


def checked_account(eng, seen):
    """``eng._account`` held, at every dispatch, to the eager function on
    the WHOLE pool and to leaving the planes where they are."""
    inner = eng._account

    def account(program, pool, *args, **static):
        # eager, on the whole pool, page length read off a plane
        want = EAGER[program](pool, *args)
        before = plane_buffers(pool)
        got = inner(program, pool, *args, **static)
        assert set(got) == set(pool)
        for key in kv_pages.ACCOUNTING:
            assert got[key].dtype == want[key].dtype, key
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)
        assert plane_buffers(got) == before
        seen[program] = seen.get(program, 0) + 1
        return got

    eng._account = account


@pytest.mark.parametrize("variant", VARIANTS)
def test_accounting_equals_the_eager_pool_functions_over_a_seeded_sweep(
        models, variant):
    """The sweep of ``tests/test_serve_prefix.py``: shared-prefix traffic
    against a TIGHT pool, so that admissions, evictions, copy-on-write
    adoptions, releases (and under speculation rollbacks of both pools)
    interleave; the pool invariant holds after every step."""
    vocab = models[VARIANTS[variant]["family"]][0].vocab_size
    seen: dict = {}
    for seed in (0, 1):
        rng = np.random.RandomState(seed)
        eng = make_engine(models, variant)
        checked_account(eng, seen)
        prefixes = [
            [int(x) for x in rng.randint(1, vocab, size=6)] for _ in range(3)
        ]
        for _ in range(24):
            if rng.uniform() < 0.6:
                suffix = [int(x) for x in rng.randint(1, vocab, size=2)]
                eng.submit(eng.make_request(
                    prefixes[int(rng.randint(len(prefixes)))] + suffix,
                    int(rng.randint(1, 4)),
                ))
            eng.step()
            assert_pool_invariants(eng)
        drain(eng)
        eng.step()  # flush the final releases
        assert_pool_invariants(eng)
        assert eng.pool_ok_failures == 0
    ran = {op for op in OPS if seen.get(dispatch_of(eng, op)[0])}
    # rollback exists under speculation only; the rest every sweep meets
    assert ran == set(OPS) - (set() if eng.spec_k else {"truncate"})


# --------------------- (d) adopt writes planes: whole pool, donated or not


@pytest.mark.parametrize("variant", ["dense", "latent"])
def test_adopt_with_a_copy_on_write_row_is_the_same_donated_or_not(
        models, variant):
    """A radix hit that ends inside a page (full page by reference, the
    partial page copied) through an engine that donates and one that does
    not: the same pools, the same tokens; only the donating one gives its
    old planes up."""
    prefix = [11, 12, 13, 14, 15, 16]  # a full page and half of one
    engines = {d: make_engine(models, variant, donate=d) for d in (True, False)}
    assert engines[True]._adopt is engine_mod._adopt_donating
    assert engines[False]._adopt is engine_mod._adopt
    cows = {True: [], False: []}
    for donate, eng in engines.items():
        def adopt(pool, slots, pages, cow, inner=eng._adopt, donate=donate):
            out, ok = inner(pool, slots, pages, cow)
            if (np.asarray(cow) >= 0).any():
                cows[donate].append(next(iter(
                    kv_pages.planes(pool).values())).is_deleted())
            return out, ok

        eng._adopt = adopt
    # the cold prompt leaves a node for the partial page; each hit copies it
    for tail in ([], [21, 22], [23], [24, 25]):
        tokens = {}
        for donate, eng in engines.items():
            req = eng.make_request(prefix + tail, 3)
            assert eng.submit(req) is None
            drain(eng)
            tokens[donate] = list(req.tokens)
        assert tokens[True] == tokens[False]
        a, b = (engines[d].pool for d in (True, False))
        assert set(a) == set(b)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    # both met the copy-on-write row; the donating adopt consumed its pool
    assert cows[True] and all(cows[True])
    assert cows[False] and not any(cows[False])
