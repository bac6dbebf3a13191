"""Median host-clock step (dispatch to ``block_until_ready``), in ms."""

from benchmark import stats


def read(record: dict, args: dict):
    med = stats.median(record["step_s"])
    return None if med is None else med * 1e3
