"""From a configuration file (the source's own key names) to the repo's
``LlamaConfig``, refusing what that record cannot state."""

from __future__ import annotations

from typing import Any


def llama_config(config: dict[str, Any], *, n_layers: int | None = None,
                 use_flash: bool | None = None):
    """``LlamaConfig`` for ``config`` (a parsed ``configs/<name>.json``).

    ``LlamaConfig`` derives ``ffn_dim = 4 * dmodel`` and ``head_dim =
    dmodel // num_heads`` and has no KV-head count, so a source whose
    widths do not satisfy those is refused here rather than run at other
    widths under its name."""
    from ddl25spring_tpu.utils.config import LlamaConfig

    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config["intermediate_size"] != 4 * d:
        raise ValueError("LlamaConfig fixes intermediate_size = 4 * hidden_size")
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError("LlamaConfig has no KV-head count (MHA only)")
    if config.get("rope_theta", 10000.0) != 10000.0:
        raise ValueError("models/llama.py fixes the RoPE base at 10,000")
    run = config.get("run", {})
    return LlamaConfig(
        vocab_size=config["vocab_size"], dmodel=d, num_heads=heads,
        n_layers=config["num_hidden_layers"] if n_layers is None else n_layers,
        ctx_size=config["max_position_embeddings"],
        dtype=run.get("dtype", "bfloat16"),
        use_flash=run.get("use_flash", False) if use_flash is None else use_flash,
    )


def train_placement(config: dict[str, Any], chips: int) -> tuple[int, int, int]:
    """``(data, stage, n_layers)`` of a training configuration on ``chips``
    chips: the mesh of its ``placement`` entry, and that entry's layers a
    stage (else the configuration's) times the stages."""
    place = config["placement"][str(chips)]
    stages = int(place["stage"])
    per_stage = place.get("layers_per_stage", config["run"]["layers_per_stage"])
    return int(place["data"]), stages, stages * int(per_stage)
