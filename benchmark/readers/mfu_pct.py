"""Model FLOP/s utilization of the window: tokens per second per chip
times the model's FLOPs per token (``benchmark/flops.py``), over the chip's
bf16 peak (``peaks.json``).  Recomputed operations and casts do not count."""

from benchmark import flops


def read(record: dict, args: dict):
    if not record.get("peaks"):
        return None
    m = record["model"]
    per_token = flops.train_flops_per_token(
        m["dmodel"], m["ffn_dim"], m["n_layers"], m["vocab"], m["ctx"]
    )
    rate = record["tokens"] / record["window_s"] / record["chips"]
    return 100.0 * rate * per_token / record["peaks"]["bf16_flops_per_s"]
