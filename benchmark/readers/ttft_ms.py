"""``args.q``-th percentile of the time to first token over the requests
SENT inside the window (``stats.ttft_samples_ms``), in ms."""

from benchmark import stats


def read(record: dict, args: dict):
    return stats.percentile(stats.ttft_samples_ms(record), args["q"])
