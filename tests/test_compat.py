"""Pins of the jax API facts the parallel stack leans on.

The code imports ``jax.shard_map``, ``lax.pcast`` and ``jax.typeof``
directly (jax 0.9.0 — the one installation there is).  What it RELIES on
is their varying/invariant typing: every rule pinned here is one a
program in this repo broke, or is built around —

- ``pcast`` retypes and never changes values;
- ``pcast`` of an already-varying value RAISES (the fault that kept every
  TP serve program from lowering: ``serve/engine._tp_jit`` cast pool
  buffers its in-spec had already made varying);
- an in-spec that names an axis types that input varying over it;
- a ``psum`` of a varying value is invariant (why DP's overlapped grads
  can leave through ``out_specs=P()``);
- a ``custom_vjp`` bwd rule must return the primal's own varying type
  (the bucket-barrier fault, ``parallel/bucketing._bucket_barrier``).

Then the two compiled-program probes of ``utils/compat.py``, over every
answer a backend can give.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ddl25spring_tpu.utils.compat import (
    compiled_cost_analysis,
    compiled_memory_stats,
)
from ddl25spring_tpu.utils.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh4(devices8):
    return make_mesh(devices8[:4], data=4)


def _vma_of(mesh, fn, in_spec, x):
    """The varying axes ``fn(x)`` carries inside a shard_map over
    ``mesh`` — read at trace time, nothing runs."""
    seen = []

    def body(v):
        seen.append(jax.typeof(fn(v)).vma)
        return v

    jax.eval_shape(
        shard_map(body, mesh=mesh, in_specs=(in_spec,), out_specs=in_spec),
        x,
    )
    return seen[0]


# ------------------------------------------------------------- shard_map


def test_shard_map_direct_call_runs_psum(mesh4):
    @functools.partial(
        shard_map, mesh=mesh4, in_specs=(P("data"),), out_specs=P()
    )
    def total(x):
        return lax.psum(jnp.sum(x), "data")

    out = total(jnp.arange(8.0))
    assert float(out) == pytest.approx(28.0)


def test_shard_map_partial_decorator_form(mesh4):
    """``shard_map(mesh=..., ...)`` without a function curries — the
    decorator spelling every ``@partial(shard_map, ...)`` site uses."""
    deco = shard_map(mesh=mesh4, in_specs=(P("data"),), out_specs=P("data"))
    assert callable(deco)
    doubled = deco(lambda x: x * 2)
    np.testing.assert_array_equal(
        np.asarray(doubled(jnp.arange(4.0))), [0.0, 2.0, 4.0, 6.0]
    )


def test_in_spec_naming_an_axis_types_the_input_varying(mesh4):
    """Sharded in, varying already; replicated in, invariant.  The TP
    serve programs' pool k/v enter split over the model axis — no cast
    is needed (or allowed, see below)."""
    assert _vma_of(mesh4, lambda v: v, P("data"), jnp.zeros(4)) == {"data"}
    assert _vma_of(mesh4, lambda v: v, P(), jnp.zeros(4)) == frozenset()


# ----------------------------------------------------------------- pcast


def test_pcast_is_identity_semantics(mesh4):
    """pcast never changes VALUES — it only retypes the aval."""
    @functools.partial(
        shard_map, mesh=mesh4, in_specs=(P(),), out_specs=P("data")
    )
    def body(x):
        return lax.pcast(x, "data", to="varying") + 1.0

    np.testing.assert_array_equal(
        np.asarray(body(jnp.zeros(1))), np.ones(4)
    )
    cast = lambda v: lax.pcast(v, "data", to="varying")  # noqa: E731
    assert _vma_of(mesh4, cast, P(), jnp.zeros(4)) == {"data"}


def test_pcast_of_an_already_varying_value_raises(mesh4):
    """The fault at ``serve/engine.py``'s old ``_tp_jit``: casting what
    the in-spec already typed varying is an error, not a no-op — cast
    only what is not varying yet (``bucketing._vary``)."""
    cast = lambda v: lax.pcast(v, "data", to="varying")  # noqa: E731
    with pytest.raises(ValueError, match="Unsupported pcast"):
        _vma_of(mesh4, cast, P("data"), jnp.zeros(4))

    from ddl25spring_tpu.parallel.bucketing import _vary

    once = lambda v: _vary(v, ("data",))  # noqa: E731
    assert _vma_of(mesh4, once, P("data"), jnp.zeros(4)) == {"data"}
    assert _vma_of(mesh4, once, P(), jnp.zeros(4)) == {"data"}


def test_psum_of_a_varying_value_is_invariant(mesh4):
    """...and an all_gather's result stays varying though every device
    holds the same bytes — re-typing it takes a reduction."""
    psum = lambda v: lax.psum(v, "data")  # noqa: E731
    assert _vma_of(mesh4, psum, P("data"), jnp.zeros(4)) == frozenset()
    gather = lambda v: lax.all_gather(v, "data", tiled=True)  # noqa: E731
    assert _vma_of(mesh4, gather, P("data"), jnp.zeros(4)) == {"data"}


def test_custom_vjp_bwd_must_return_the_primals_varying_type(mesh4):
    """The bucket-barrier fault: a bwd rule that returns the REDUCED
    (invariant) cotangent for a varying primal is refused at trace time;
    re-typed to the primal's type it traces, and the values are the
    reduction's."""
    def barrier(retype):
        @jax.custom_vjp
        def f(x):
            return x

        def bwd(_, ct):
            r = lax.pmean(ct, "data")
            return (lax.pcast(r, "data", to="varying") if retype else r,)

        f.defvjp(lambda x: (x, None), bwd)
        return f

    def grads(retype):
        return shard_map(
            jax.grad(lambda x: jnp.sum(barrier(retype)(x) ** 2)),
            mesh=mesh4, in_specs=(P("data"),), out_specs=P("data"),
        )(jnp.arange(4.0))

    with pytest.raises(ValueError, match="varying manual axes"):
        grads(False)
    # d/dx sum(x^2) = 2x per shard, then the mean over the four shards
    np.testing.assert_array_equal(np.asarray(grads(True)), np.full(4, 3.0))


# -------------------------------------------------- cost analysis shapes


class _CostDict:
    def cost_analysis(self):
        return {"flops": 7.5}


class _CostEmpty:
    def cost_analysis(self):
        return {}


class _CostNone:
    def cost_analysis(self):
        return None


class _CostRaises:
    def cost_analysis(self):
        raise NotImplementedError("no cost model on this backend")


def test_cost_analysis_normalizes_every_backend_answer():
    assert compiled_cost_analysis(_CostDict()) == {"flops": 7.5}
    assert compiled_cost_analysis(_CostEmpty()) is None
    assert compiled_cost_analysis(_CostNone()) is None
    assert compiled_cost_analysis(_CostRaises()) is None


def test_cost_analysis_returns_a_fresh_dict():
    """Mutating the normalized dict must not corrupt a cached analysis."""
    src = _CostDict()
    d = compiled_cost_analysis(src)
    d["flops"] = -1
    assert compiled_cost_analysis(src) == {"flops": 7.5}


# ------------------------------------------------- memory analysis shapes


class _MemOld:
    """CompiledMemoryStats of a backend that reports no peak."""

    argument_size_in_bytes = 1000
    output_size_in_bytes = 300
    temp_size_in_bytes = 700
    alias_size_in_bytes = 100
    generated_code_size_in_bytes = 50


class _MemNew(_MemOld):
    peak_memory_in_bytes = 4242


def _compiled_with(stats):
    class C:
        def memory_analysis(self):
            return stats

    return C()


def test_memory_stats_assembles_peak_where_the_backend_reports_none():
    out = compiled_memory_stats(_compiled_with(_MemOld()))
    assert out["peak_hbm_bytes"] == 1000 + 300 + 700 + 50 - 100
    assert out["alias_size_in_bytes"] == 100

    class Zero(_MemOld):
        peak_memory_in_bytes = 0  # reported, but empty: assemble too

    out = compiled_memory_stats(_compiled_with(Zero()))
    assert out["peak_hbm_bytes"] == 1000 + 300 + 700 + 50 - 100


def test_memory_stats_prefers_backend_peak():
    out = compiled_memory_stats(_compiled_with(_MemNew()))
    assert out["peak_hbm_bytes"] == 4242


def test_memory_stats_degrades_to_none():
    class NoApi:
        pass

    class Raises:
        def memory_analysis(self):
            raise NotImplementedError

    assert compiled_memory_stats(NoApi()) is None
    assert compiled_memory_stats(_compiled_with(None)) is None
    assert compiled_memory_stats(Raises()) is None
    # an object with none of the known fields: no stats, not zeros
    class Alien:
        irrelevant = 1

    assert compiled_memory_stats(_compiled_with(Alien())) is None


# --------------------------------------------- end-to-end on this jax


def test_both_probes_work_on_a_real_compiled_program():
    compiled = (
        jax.jit(lambda a: (a @ a).sum()).lower(jnp.ones((64, 64))).compile()
    )
    cost = compiled_cost_analysis(compiled)
    assert cost and cost.get("flops", 0) >= 2 * 64**3
    mem = compiled_memory_stats(compiled)
    if mem is not None:  # some backends expose no memory stats at all
        assert mem["peak_hbm_bytes"] > 0
