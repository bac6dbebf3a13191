"""The main path's programs, compiled for a TPU v5e that is DESCRIBED, not
attached (``on-chip-measurement`` guide §2, third rehearsal).

Interpret-mode tests cannot see what the chip's compiler refuses: a slice
off the tiling, too much fast memory, a program that does not fit 16 GB.
These compile the kernels and the step programs ``chip_smoke.py`` runs, at
the widths it runs them, in this process, without a chip.  A compile that
passes is not a chip run and is never reported as one.

Rules this file keeps (the guide says why): the topology is described
inside a module-scoped, non-autouse fixture — never at import, in a
``skipif`` or a ``parametrize`` argument, or in ``conftest.py``; nothing
built from it exists outside a fixture or a test; every compile happens in
the test's own process; the persistent compile cache is off around them (a
described-device executable cannot be read back without a chip).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The program asks ``jax.default_backend()`` to choose the kernel
    over interpret mode / the dense path; with a described chip the
    attached backend is still the CPU.  The test steers that here — the
    program has no option for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


# --------------------------------------------------------------- kernels

# [B, L, H, hd]: the reference LLaMA's own width (head_dim 48 — not a
# multiple of 128), a long-ish context, and the 32k-token single sequence
FLASH_SHAPES = [(8, 256, 6, 48), (2, 2048, 8, 128), (1, 32768, 4, 128)]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize(
    "shape", FLASH_SHAPES, ids=lambda s: "x".join(map(str, s))
)
def test_flash_attention_compiles_for_v5e(one_chip, shape, direction):
    from ddl25spring_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    lowered = jax.jit(fwd if direction == "fwd" else bwd).lower(x, x, x)
    assert "tpu_custom_call" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("held_in", ["float32", "bfloat16"])
def test_gdn_step_compiles_for_v5e_and_updates_the_state_in_place(
        one_chip, as_on_tpu, held_in):
    """The recurrent-state kernel at the served widths (128 slots, 6 state
    layers, 32 heads of 128 x 128): the chip's compiler takes it, a trace
    will name it, and the donated state is updated where it lies: no copy
    of the 1.6 GB array among the temporaries."""
    from ddl25spring_tpu.ops.gdn import gdn_step

    S, L, H, d = 128, 6, 32, 128

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = arg((S, L, H, d, d), jnp.dtype(held_in))
    lowered = jax.jit(gdn_step, donate_argnums=(0,)).lower(
        state, arg((), jnp.int32), arg((S, H, d)), arg((S, H, d)),
        arg((S, H, d)), arg((S, H), jnp.float32), arg((S, H), jnp.float32),
        arg((S,), jnp.bool_))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "gdn_step" in text
    mem = lowered.compile().memory_analysis()
    nbytes = S * L * H * d * d * state.dtype.itemsize
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // 100


# (rows a call, layers, experts held, hidden, expert width): a tick of the
# latent cell (64 slots x top-4 of 32 held), of the hybrid cell (128 x top-10
# of 128) and of the top-1 cell (64 x top-1 of 16: every expert of a layer
# held, four rows an expert), and the top-1 cell's widest prompt pass
MOE_GMM_REGIMES = {
    "latent_tick": (256, 6, 32, 4096, 2048),
    "hybrid_tick": (1280, 8, 128, 2048, 512),
    "top1_tick": (64, 20, 16, 2048, 2048),
    "top1_pass": (4096, 20, 16, 2048, 2048),
}


@pytest.mark.parametrize("regime", list(MOE_GMM_REGIMES))
def test_moe_gmm_compiles_for_v5e_in_every_served_regime(
        one_chip, as_on_tpu, regime):
    """One kernel, three expert shapes: a tile or a buffer chosen for one is
    compiled for the other two here, at the served widths, with the whole
    stack as the operand (no layer sliced out: no temporary near a layer's
    size)."""
    from ddl25spring_tpu.ops.moe_gmm import moe_gmm

    rows, L, E, D, F = MOE_GMM_REGIMES[regime]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for k, n in ((D, F), (F, D)):  # gate / up, then down
        lowered = jax.jit(moe_gmm).lower(
            arg((rows, k)), arg((L, E, k, n)), arg((E,), jnp.int32),
            arg((), jnp.int32))
        text = lowered.as_text()
        assert "tpu_custom_call" in text and "moe_gmm" in text
        mem = lowered.compile().memory_analysis()
        assert mem.temp_size_in_bytes < E * k * n * 2 // 4


@pytest.mark.parametrize("direction,kernels", [
    ("fwd", ["flash_fwd"]),
    ("bwd", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
])
def test_flash_kernels_keep_their_names_for_v5e(one_chip, direction, kernels):
    """Each ``pallas_call`` is named: the name is the custom call's
    ``kernel_name`` and a component of its ``op_name``, which is how a
    trace's events are told apart (``benchmark/tools/trace_scopes.py``);
    unnamed they all read ``tpu_custom_call``.  Lowered, not compiled."""
    from ddl25spring_tpu.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((2, 2048, 8, 128), jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    text = jax.jit(fwd if direction == "fwd" else bwd).lower(x, x, x).as_text(
        debug_info=True
    )
    assert text.count("tpu_custom_call") >= len(kernels)
    for name in kernels:
        assert f'kernel_name = "{name}"' in text
        assert re.search(rf"[(/]{name}\)*/pallas_call", text), name


def test_flash_attention_with_lse_compiles_for_v5e(one_chip):
    from ddl25spring_tpu.ops.flash_attention import flash_attention_with_lse

    x = jax.ShapeDtypeStruct((2, 2048, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    fn = jax.jit(
        lambda q, k, v: flash_attention_with_lse(q, k, v, interpret=False)
    )
    lowered = fn.lower(x, x, x)
    assert "tpu_custom_call" in lowered.as_text()
    assert len(lowered.compile().output_shardings) == 2  # (o, lse)


# ------------------------------------------------------------ serve path


@pytest.fixture(scope="module")
def ref_serve(one_chip):
    """``bench.py --serve --serve-model ref``'s model, pool geometry and
    the abstract arguments of its two programs."""
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.serve import driver, kv_pages

    cfg = driver.serve_model("ref")
    knobs = driver.engine_knobs()
    pages_per_seq = -(-cfg.ctx_size // knobs["page_len"])
    params = jax.eval_shape(
        lambda: llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    )
    pool = jax.eval_shape(lambda: kv_pages.init_page_pool(
        cfg, n_pages=knobs["n_pages"], page_len=knobs["page_len"],
        max_slots=knobs["max_slots"], pages_per_seq=pages_per_seq,
    ))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return cfg, knobs, *_abstract((params, pool, key), one_chip)


def test_paged_decode_tick_compiles_for_v5e(ref_serve):
    from ddl25spring_tpu.serve.engine import make_decode_tick

    cfg, knobs, params, pool, key = ref_serve
    tick = jax.jit(
        make_decode_tick(cfg, temperature=0.0, sentinel=False),
        donate_argnums=(1,),
    )
    compiled = tick.lower(params, pool, key).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


def test_prefill_program_compiles_for_v5e(ref_serve, one_chip):
    from ddl25spring_tpu.serve.engine import make_prefill

    cfg, knobs, params, pool, key = ref_serve
    B, Lp = knobs["prefill_batch"], knobs["max_prompt_len"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    prefill = jax.jit(
        make_prefill(cfg, max_prompt_len=Lp, temperature=0.0,
                     sentinel=False),
        donate_argnums=(1,),
    )
    compiled = prefill.lower(
        params, pool, i32(B, Lp), i32(B), i32(B), i32(B), key
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


# ----------------------------------------------------------- train steps


def test_llama_ref_train_step_compiles_with_the_kernel(topo, as_on_tpu):
    """``lab/s01_b2_dp_pp.py --workload llama`` on one chip: the jitted
    pipeline step (mesh ``data=1, stage=1``) at the reference constants,
    flash ON — and the kernel is in the lowered text, not the dense
    path."""
    import optax

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import make_pipeline_train_step
    from ddl25spring_tpu.utils.config import LlamaConfig, replace

    cfg = replace(LlamaConfig(), use_flash=True)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "stage"))
    rep = NamedSharding(mesh, P())
    tx = optax.adam(8e-4)
    staged = jax.eval_shape(lambda: llama.split_blocks_for_stages(
        llama.init_llama_params(jax.random.PRNGKey(0), cfg), 1
    ))
    opt_state = jax.eval_shape(tx.init, staged)
    staged, opt_state = _abstract((staged, opt_state), rep)
    tokens = jax.ShapeDtypeStruct((24, cfg.ctx_size), jnp.int32, sharding=rep)
    step = make_pipeline_train_step(cfg, tx, mesh, 3)
    lowered = step.lower(staged, opt_state, tokens)
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in text
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.slow  # ~60 s of TPU compile alone; chip_smoke.py runs this
# very step on the chip, and the LLaMA step above keeps a whole train
# step in the tier-1 run
def test_resnet18_step_at_batch_1024_fits_a_v5e(topo):
    """``python bench.py`` on one chip: the ResNet-18 DP step at per-chip
    batch 1024 (bf16 — the builder reads the device's platform), under
    the chip's 16 GB.  Compiled by hand before the first chip run."""
    from ddl25spring_tpu.benchmarks import build_resnet_step

    step, params, opt_state, meta = build_resnet_step(
        topo.devices[:1], 1, 1, 1, 1024
    )
    rep = NamedSharding(meta["mesh"], P())
    params, opt_state = _abstract((params, opt_state), rep)
    raw = (
        jax.ShapeDtypeStruct((1024, 32, 32, 3), jnp.uint8, sharding=rep),
        jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=rep),
    )
    mem = step.lower(params, opt_state, raw).compile().memory_analysis()
    total = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < total < V5E_HBM_BYTES, mem
