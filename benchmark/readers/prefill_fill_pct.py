"""How full the prefill passes are: prompt positions written over the
positions the padded scans ran (``prefill_batch x (max_prompt_len -
start)`` a pass), summed over the passes begun in the window.  The engine
samples both at each pass (``serve.prefill.prompt_tokens``,
``serve.prefill.scanned_positions``)."""

from benchmark import ring


def read(record: dict, args: dict):
    tokens = ring.total(record, "serve.prefill.prompt_tokens")
    scanned = ring.total(record, "serve.prefill.scanned_positions")
    if tokens is None or not scanned:
        return None
    return 100.0 * tokens / scanned
