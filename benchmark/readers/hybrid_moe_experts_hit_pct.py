"""Share of the held experts that a pass reads, for a configuration whose
file counts its experts under ``num_experts``: the window's sum of
``serve.moe.experts_hit`` (a pass's count of (layer, held expert) pairs with
at least one live assignment) over passes x layers x experts held, as the
configuration's file states them.  (``moe_experts_hit_pct`` reads the same
ring under the other family's key; a ``benchmark`` PR may merge the two.)"""

from benchmark import ring


def read(record: dict, args: dict):
    hit = ring.series(record, "serve.moe.experts_hit")
    if not hit:
        return None
    config = record["config"]
    pairs = config["num_hidden_layers"] * config["num_experts"]
    return 100.0 * sum(v for _, v in hit) / (len(hit) * pairs)
