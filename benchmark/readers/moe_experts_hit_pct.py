"""Share of the held experts that a pass reads: the window's sum of
``serve.moe.experts_hit`` (a pass's count of (layer, held expert) pairs
with at least one live assignment) over passes x layers x experts held.
Decode ticks outnumber prompt passes ten to one, so this is nearly the
ticks' share: the weights a tick must stream."""

from benchmark import ring


def read(record: dict, args: dict):
    hit = ring.series(record, "serve.moe.experts_hit")
    if not hit:
        return None
    config = record["config"]
    pairs = config["num_hidden_layers"] * config["n_routed_experts"]
    return 100.0 * sum(v for _, v in hit) / (len(hit) * pairs)
