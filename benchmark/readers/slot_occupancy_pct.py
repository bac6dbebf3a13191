"""Mean share of the engine's decode slots that hold a request, over the
window's decode passes (``serve.active_slots``, sampled as each pass
starts) and the cell's ``engine.max_slots``."""

from benchmark import ring


def read(record: dict, args: dict):
    active = ring.series(record, "serve.active_slots")
    if not active:
        return None
    mean = sum(v for _, v in active) / len(active)
    return 100.0 * mean / record["cell"]["engine"]["max_slots"]
