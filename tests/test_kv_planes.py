"""Every ``kv_pages`` op over pools of two and of three planes: no op
learns what a plane means, every op that moves page contents walks all of
them, and under any seeded interleaving ``used + free == n_pages`` with
``free == (refcount == 0)``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.utils.config import LlamaConfig

CFG = LlamaConfig(vocab_size=64, dmodel=16, num_heads=2, n_layers=2,
                  ctx_size=32, dtype="float32")
PLANES = {
    "dense k and v": {"k": (2, 8), "v": (2, 8)},
    "latent and rotary": {"ckv": (8,), "kpe": (4,)},
    "three planes": {"a": (3,), "b": (2, 2), "state": ()},
}
N_PAGES, PAGE, SLOTS, PER_SEQ = 12, 4, 3, 4


def make_pool(planes):
    return kv_pages.init_page_pool(
        CFG, n_pages=N_PAGES, page_len=PAGE, max_slots=SLOTS,
        pages_per_seq=PER_SEQ, planes=planes)


def check(pool):
    free = np.asarray(pool["free"])
    rc = np.asarray(pool["refcount"])
    assert (free == (rc == 0)).all() and (rc >= 0).all()
    assert int(kv_pages.used_pages(pool)) + int(free.sum()) == N_PAGES


@pytest.mark.parametrize("name", PLANES)
def test_pool_holds_what_the_model_declares(name):
    pool = make_pool(PLANES[name])
    assert set(kv_pages.planes(pool)) == set(PLANES[name])
    assert set(pool) == set(PLANES[name]) | set(kv_pages.ACCOUNTING)
    for plane, shape in PLANES[name].items():
        assert pool[plane].shape == (N_PAGES + 1, CFG.n_layers, PAGE, *shape)
    assert kv_pages.page_len_of(pool) == PAGE
    assert kv_pages.pool_geometry(pool)["max_seq_len"] == PER_SEQ * PAGE
    with pytest.raises(ValueError, match="accounting"):
        make_pool({"free": (2,)})
    with pytest.raises(ValueError, match="at least one plane"):
        make_pool({})


@pytest.mark.parametrize("name", PLANES)
def test_write_gather_and_copy_on_write_walk_every_plane(name):
    pool = make_pool(PLANES[name])
    pool, ok = kv_pages.reserve_pages(
        pool, jnp.arange(SLOTS), jnp.zeros(SLOTS, jnp.int32),
        jnp.asarray([True, False, False]))
    assert bool(ok)
    src = int(pool["page_table"][0, 0])
    values = {
        p: jax.random.normal(jax.random.PRNGKey(i), (1, 3, *shape))
        for i, (p, shape) in enumerate(PLANES[name].items())
    }
    pages = jnp.full((1, 3), src)
    offs = jnp.arange(3)[None, :]
    planes = kv_pages.write_planes(kv_pages.planes(pool), 1, pages, offs, values)
    view = kv_pages.gather_planes(planes, 1, jnp.asarray([[src]]))
    for p in PLANES[name]:
        assert view[p].shape == (1, PAGE, *PLANES[name][p])
        np.testing.assert_array_equal(view[p][:, :3], values[p])
    pool = kv_pages.with_planes(pool, planes)
    pool, ok = kv_pages.adopt_prefix(
        pool, jnp.asarray([1, -1]), jnp.full((2, PER_SEQ), -1, jnp.int32),
        jnp.asarray([src, -1]))
    assert bool(ok)
    copy = int(pool["page_table"][1, 0])
    assert copy != src
    for p in PLANES[name]:  # bit for bit, in every plane
        np.testing.assert_array_equal(pool[p][copy], pool[p][src])
    check(pool)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", PLANES)
def test_accounting_invariant_under_seeded_interleavings(name, seed):
    """reserve / adopt (by reference and copy-on-write) / ref / unref /
    truncate / release in a seeded random order, on every set of planes:
    the planes' shapes change nothing of the accounting."""
    rng = np.random.default_rng(seed)
    pool = make_pool(PLANES[name])
    ops = {k: jax.jit(getattr(kv_pages, k)) for k in (
        "reserve_pages", "adopt_prefix", "ref_pages", "unref_pages",
        "truncate_to", "release_slots")}
    claimed: list[int] = []  # references taken with ref_pages
    for _ in range(40):
        op = rng.choice(list(ops))
        used = np.flatnonzero(~np.asarray(pool["free"]))
        if op == "reserve_pages":
            pos = jnp.asarray(rng.integers(0, PER_SEQ * PAGE, SLOTS), jnp.int32)
            table = np.asarray(pool["page_table"])
            need = np.asarray([table[s, int(pos[s]) // PAGE] < 0 for s in range(SLOTS)])
            pool, _ok = ops[op](pool, jnp.arange(SLOTS), pos,
                                jnp.asarray(need & (rng.random(SLOTS) < 0.7)))
        elif op == "adopt_prefix" and used.size:
            slot = int(rng.integers(0, SLOTS))
            if (np.asarray(pool["page_table"])[slot] >= 0).any():
                continue  # adopt seats a prefix in an EMPTY table only
            adopt = np.full((1, PER_SEQ), -1, np.int32)
            n_ref = int(rng.integers(0, 3))
            adopt[0, :n_ref] = rng.choice(used, n_ref)
            cow = int(rng.choice(used)) if rng.random() < 0.5 else -1
            pool, _ok = ops[op](pool, jnp.asarray([slot]), jnp.asarray(adopt),
                                jnp.asarray([cow]))
        elif op == "ref_pages" and used.size:
            page = int(rng.choice(used))
            claimed.append(page)
            pool = ops[op](pool, jnp.asarray([page, -1]))
        elif op == "unref_pages" and claimed:
            pool = ops[op](pool, jnp.asarray([claimed.pop(), -1]))
        elif op == "truncate_to":
            pool = ops[op](pool, jnp.asarray(rng.integers(0, 12, SLOTS), jnp.int32),
                           jnp.asarray(rng.random(SLOTS) < 0.5))
        elif op == "release_slots":
            pool = ops[op](pool, jnp.asarray(rng.random(SLOTS) < 0.4))
        check(pool)
        assert set(kv_pages.planes(pool)) == set(PLANES[name])
    # let everything go: every page comes back
    pool = kv_pages.release_slots(pool, jnp.ones(SLOTS, bool))
    if claimed:
        pool = kv_pages.unref_pages(pool, jnp.asarray(claimed))
    check(pool)
    assert int(kv_pages.used_pages(pool)) == 0
