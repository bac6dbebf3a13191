"""Stable names in every compiled program: the ``jax.named_scope`` parts
of the model, the pipeline step and the serving programs are in the
``op_name`` of the lowered operations (forward AND transposed), and they
are metadata only — the operations are the same without them."""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serve import kv_pages
from ddl25spring_tpu.serve.engine import make_decode_tick, make_prefill
from ddl25spring_tpu.utils.config import LlamaConfig
from ddl25spring_tpu.utils.mesh import make_mesh

CFG = LlamaConfig(
    vocab_size=64, dmodel=16, num_heads=2, n_layers=2, ctx_size=16,
    dtype="float32",
)
OP_NAME = re.compile(r'op_name="([^"]+)"')


def scopes(text: str) -> dict[str, set[str]]:
    """``{scope: {"fwd", "bwd", ""}}`` over a compiled program's
    ``op_name``s: the phase is ``bwd`` under a ``transpose(``, ``fwd``
    under a ``jvp(``."""
    out: dict[str, set[str]] = {}
    for op_name in OP_NAME.findall(text):
        parts = op_name.split("/")
        phase = ("bwd" if any("transpose(" in p for p in parts)
                 else "fwd" if any("jvp(" in p for p in parts) else "")
        for p in parts:
            out.setdefault(re.sub(r"^(?:[a-z_]+\()*|\)*$", "", p), set()).add(phase)
    return out


def without_metadata(text: str) -> str:
    """The computations' instruction lines less their ``metadata={...}``
    (the module's header tables of file names and stack frames go too)."""
    lines = [ln for ln in text.splitlines()
             if re.match(r"\s*(ROOT |ENTRY )?%|}", ln)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.fixture(scope="module")
def train_step_text(devices8):
    from ddl25spring_tpu.parallel.pipeline import (
        make_pipeline_train_step,
        shard_staged_params,
    )

    mesh = make_mesh(devices8[:4], data=2, stage=2)
    tx = optax.adam(1e-3)
    staged = shard_staged_params(
        llama.split_blocks_for_stages(
            llama.init_llama_params(jax.random.PRNGKey(0), CFG), 2
        ), mesh,
    )
    step = make_pipeline_train_step(
        CFG, tx, mesh, 2, data_axis="data", donate=False
    )
    tokens = jnp.zeros((4, CFG.ctx_size), jnp.int32)
    return step.lower(staged, tx.init(staged), tokens).compile().as_text()


@pytest.mark.parametrize("scope", ["embed", "blocks", "attn", "mlp", "head_loss"])
def test_model_scopes_are_in_the_train_step_forward_and_transposed(
    train_step_text, scope
):
    assert {"fwd", "bwd"} <= scopes(train_step_text)[scope]


def test_head_loss_is_scoped_once(train_step_text):
    """One scope around the last stage's ``cond``; none nested in it."""
    paths = [n.split("/") for n in OP_NAME.findall(train_step_text)]
    assert any("head_loss" in p for parts in paths for p in parts)
    assert all(sum("head_loss" in p for p in parts) <= 1 for parts in paths)


@pytest.mark.parametrize(
    "scope,phases", [("schedule", {"fwd", "bwd"}), ("stage_permute", {"fwd", "bwd"}),
                     ("grad_allreduce", {"bwd"}), ("optimizer", {""})],
)
def test_step_scopes_are_in_the_train_step(train_step_text, scope, phases):
    assert phases <= scopes(train_step_text)[scope]
    named = [line for line in train_step_text.splitlines() if f"/{scope}/" in line]
    if scope == "stage_permute":
        assert any("collective-permute" in line for line in named)
    if scope == "grad_allreduce":  # the head's gradients, over data
        assert any("all-reduce" in line for line in named)


def serve_programs(named: bool):
    """(tick text, prefill text), with the scopes or with
    ``jax.named_scope`` switched off."""
    ctx = contextlib.nullcontext
    with pytest.MonkeyPatch.context() as mp:
        if not named:
            mp.setattr(jax, "named_scope", lambda name: ctx())
        params = llama.init_llama_params(jax.random.PRNGKey(0), CFG)
        pool = kv_pages.init_page_pool(
            CFG, n_pages=8, page_len=4, max_slots=2, pages_per_seq=4
        )
        key = jax.random.PRNGKey(0)
        tick = jax.jit(make_decode_tick(CFG, sentinel=False)).lower(
            params, pool, key
        )
        prefill = jax.jit(
            make_prefill(CFG, max_prompt_len=8, sentinel=False)
        ).lower(
            params, pool, jnp.zeros((2, 8), jnp.int32),
            jnp.full((2,), 3, jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.arange(2, dtype=jnp.int32), key,
        )
        return tick.compile().as_text(), prefill.compile().as_text()


@pytest.fixture(scope="module")
def serve_texts():
    return serve_programs(named=True)


@pytest.mark.parametrize("program", ["tick", "prefill"])
def test_serving_programs_carry_every_scope(serve_texts, program):
    text = serve_texts[program == "prefill"]
    found = scopes(text)
    for scope in ("embed", "blocks", "page_write", "page_gather", "attn",
                  "mlp", "head", "sample"):
        assert scope in found, (program, scope)


def test_scopes_are_metadata_only(serve_texts):
    """The same operations with ``jax.named_scope`` switched off: what the
    default, untraced run executes is what it executed before."""
    bare = serve_programs(named=False)
    assert "/attn/" not in bare[0] and "/attn/" in serve_texts[0]
    for with_names, without in zip(serve_texts, bare):
        assert without_metadata(with_names) == without_metadata(without)
