"""Config dataclasses.

The reference keeps hyperparameters as module-level constants
(``dmodel=288 ... batch_size=3`` at ``lab/s01_b1_microbatches.py:21-26``) and
the rank as the only CLI arg.  Here each workload gets a small frozen
dataclass; mesh topology replaces ranks.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class LlamaConfig:
    """Reference workload constants: ``lab/s01_b1_microbatches.py:21-26``."""

    vocab_size: int = 4096
    dmodel: int = 288
    num_heads: int = 6
    n_layers: int = 6
    ctx_size: int = 256
    pad_id: int = 0
    # MXU-friendly compute dtype.  Training keeps float32 masters and
    # casts at each use; the paged server holds its matrices in this type
    # (``PagedModel.resident``, serve/paged_model.py)
    dtype: str = "bfloat16"
    use_flash: bool = False     # Pallas flash-attention kernel for the hot op
    n_experts: int = 0          # > 0: switch-MoE FFN in every block
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance aux loss weight
    moe_top_k: int = 1          # experts/token: 1 = switch, 2 = Mixtral-style

    @property
    def head_dim(self) -> int:
        return self.dmodel // self.num_heads

    @property
    def ffn_dim(self) -> int:
        return 4 * self.dmodel

    def paged_model(self):
        """What this model offers the paged server
        (``serve/paged_model.py``): the dense block's, or ``None``."""
        from ddl25spring_tpu.models.llama_paged import paged_model

        return paged_model(self)


@dataclass(frozen=True)
class PipelineConfig:
    """Reference: 3 stages x 3 microbatches, batch 3, Adam lr=8e-4
    (``lab/s01_b1_microbatches.py:24-26,64,66``; ``lab/run-b1.sh``)."""

    num_stages: int = 3
    num_microbatches: int = 3
    batch_size: int = 3
    learning_rate: float = 8e-4


@dataclass(frozen=True)
class DpPpConfig:
    """Reference: 2 pipelines x 3 stages, world 6
    (``lab/s01_b2_dp_pp.py:22-34``)."""

    data: int = 2
    num_stages: int = 3
    num_microbatches: int = 3
    per_replica_batch: int = 3
    learning_rate: float = 8e-4


@dataclass(frozen=True)
class FlConfig:
    """Tutorial defaults: lr=0.01, E=1, B=100, 10 rounds, seed=10
    (``lab/homework-1.ipynb`` cell 5; BASELINE.md)."""

    nr_clients: int = 10
    client_fraction: float = 0.1
    batch_size: int = 100      # -1 = full batch (FedSGD)
    nr_local_epochs: int = 1
    learning_rate: float = 0.01
    nr_rounds: int = 10
    iid: bool = True
    seed: int = 10


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


def env_flag(name: str, default: bool = False) -> bool:
    """Read a boolean ``DDL25_*`` switch from the process environment.

    This is the sanctioned env boundary for every runtime toggle the
    library honors: modules that build traced computations must not read
    ``os.environ`` themselves (``tools/graft_lint.py`` rule S101 — a
    compiled program's structure silently depending on ambient process
    state is exactly the hazard class the linter exists for) and instead
    route through here, so every env-dependent default is greppable in
    one place.  Unset -> ``default``; ``""``/``"0"``/``"false"`` ->
    False; anything else -> True.
    """
    import os

    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw not in ("", "0", "false")


def env_choice(name: str, choices: tuple[str, ...], default: str) -> str:
    """Read an enumerated ``DDL25_*`` setting (same sanctioned boundary
    as :func:`env_flag`).  Unset/empty -> ``default``; a value outside
    ``choices`` raises immediately — a typo'd policy silently falling
    back to the default is exactly how a guard rail fails unnoticed."""
    import os

    raw = os.environ.get(name)
    if not raw:
        return default
    if raw not in choices:
        raise ValueError(
            f"{name}={raw!r} is not one of {sorted(choices)}"
        )
    return raw


def env_str(name: str, default: str | None = None) -> str | None:
    """Read a free-form string ``DDL25_*`` setting through the
    sanctioned env boundary (see :func:`env_flag`).  Unset/empty ->
    ``default``.  Exists so host-side drivers (``ft.chaos.from_env``)
    never touch ``os.environ`` from a traced-scope module (rule S101 —
    the scope grew to ``ft/`` in PR 9)."""
    import os

    raw = os.environ.get(name)
    return raw if raw else default


def env_float(name: str, default: float) -> float:
    """Read a float ``DDL25_*`` setting through the sanctioned env
    boundary (see :func:`env_flag`).  Unset/empty -> ``default``."""
    import os

    raw = os.environ.get(name)
    if not raw:
        return default
    return float(raw)


def env_int(name: str, default: int) -> int:
    """Read an integer ``DDL25_*`` setting through the sanctioned env
    boundary (see :func:`env_flag`).  Unset/empty -> ``default``; a
    non-integer value raises immediately (a typo'd byte count silently
    falling back would make e.g. a bucket-size sweep recommendation
    look applied when it wasn't)."""
    import os

    raw = os.environ.get(name)
    if not raw:
        return default
    return int(raw)
