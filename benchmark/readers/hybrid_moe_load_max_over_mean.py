"""Imbalance of the routed load over the held experts, for a configuration
whose file counts its experts under ``num_experts``: the busiest held
expert's assignments over the mean's, layer by layer, over the window
(``serve.moe.load_max`` sums a pass's per-layer maxima;
``serve.moe.assignments_here`` over the experts held is the sum of its
per-layer means).  1 is even; no capacity, so a high reading costs time and
never a token."""

from benchmark import ring


def read(record: dict, args: dict):
    top = ring.total(record, "serve.moe.load_max")
    here = ring.total(record, "serve.moe.assignments_here")
    if not top or not here:
        return None
    return top * record["config"]["num_experts"] / here
