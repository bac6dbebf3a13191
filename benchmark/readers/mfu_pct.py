"""Model FLOP/s utilization of the window: tokens per second per chip
times the model's FLOPs per token (the family's count, in the record), over
the chip's bf16 peak (``peaks.json``).  Recomputed operations and casts do
not count."""


def read(record: dict, args: dict):
    if not record.get("peaks"):
        return None
    rate = record["tokens"] / record["window_s"] / record["chips"]
    return 100.0 * rate * record["flops_per_token"] / record["peaks"]["bf16_flops_per_s"]
