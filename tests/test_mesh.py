"""Mesh construction, including the multi-host hybrid builder's
single-process fallback."""

import jax
import pytest

from ddl25spring_tpu.utils.mesh import (
    make_hybrid_mesh,
    make_mesh,
    mesh_axis_sizes,
)


def test_make_mesh_infer_axis(devices8):
    mesh = make_mesh(devices8, data=-1, stage=2)
    assert mesh_axis_sizes(mesh) == {"data": 4, "stage": 2}


def test_make_mesh_too_many_devices_raises(devices8):
    with pytest.raises(ValueError):
        make_mesh(devices8[:2], data=4)


def test_hybrid_mesh_single_process_fallback(devices8):
    # one process (this test environment): DCN axes collapse into a flat
    # mesh with the same axis names/sizes, so code written for the hybrid
    # topology runs unchanged on a single host
    assert jax.process_count() == 1
    mesh = make_hybrid_mesh({"data": 2}, stage=2, model=2)
    assert mesh_axis_sizes(mesh) == {"data": 2, "stage": 2, "model": 2}
    assert tuple(mesh.axis_names) == ("data", "stage", "model")


def test_hybrid_mesh_forced_slices_layout(devices8):
    """force_slices simulates 2 slices of 4: the dcn axis must be
    OUTERMOST (each dcn index owns one contiguous slice block), so
    cross-slice collectives only ever ride the dcn axis."""
    mesh = make_hybrid_mesh({"data": 2}, force_slices=2, stage=4)
    assert mesh_axis_sizes(mesh) == {"data": 2, "stage": 4}
    devs = jax.devices()
    # row i of the mesh grid == simulated slice i (contiguous ids)
    for i in range(2):
        assert list(mesh.devices[i]) == devs[i * 4 : (i + 1) * 4]
    # partial ici footprint stays within its slice
    mesh_p = make_hybrid_mesh({"data": 2}, force_slices=2, stage=2)
    assert list(mesh_p.devices[1]) == devs[4:6]

    with pytest.raises(ValueError, match="simulated slices"):
        make_hybrid_mesh({"data": 3}, force_slices=3)


def test_hybrid_mesh_dp_over_dcn_pp_over_ici_trains(devices8):
    """One DP-over-DCN x PP-over-ICI train step on the simulated 2-slice
    mesh (VERDICT r3 #8): the flagship topology laid out so the gradient
    pmean is the only cross-slice collective while the per-tick ppermute
    stays inside a slice."""
    import jax.numpy as jnp
    import optax

    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step,
        shard_staged_params,
    )
    from ddl25spring_tpu.utils.config import LlamaConfig

    mesh = make_hybrid_mesh({"data": 2}, force_slices=2, stage=4)
    cfg = LlamaConfig(
        vocab_size=64, dmodel=32, num_heads=2, n_layers=4, ctx_size=16,
        dtype="float32",
    )
    params = llama.init_llama_params(jax.random.PRNGKey(0), cfg)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(params, 4), mesh
    )
    tx = optax.adam(1e-3)
    step = make_pipeline_train_step(
        cfg, tx, mesh, num_microbatches=2, data_axis="data"
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    _, _, loss = step(staged, tx.init(staged), tokens)
    assert float(loss) > 0 and jnp.isfinite(loss)
