"""Share of the window the engine spent inside prefill passes: the sum
over DISTINCT prefill dispatches that began in the window of their wall,
over the window.  A dispatch serves up to ``prefill_batch`` requests and
is counted once (``stats.distinct_prefills``)."""

from benchmark import stats


def read(record: dict, args: dict):
    passes = stats.distinct_prefills(
        record["requests"], record["t_open"], record["t_close"]
    )
    return 100.0 * sum(wall for _, wall in passes) / record["window_s"]
