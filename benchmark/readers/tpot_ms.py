"""``args.q``-th percentile of the time per output token over the requests
sent AND completed inside the window (``stats.tpot_samples_ms``), in ms."""

from benchmark import stats


def read(record: dict, args: dict):
    return stats.percentile(stats.tpot_samples_ms(record), args["q"])
