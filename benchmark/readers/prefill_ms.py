"""Median wall of one prefill pass (``Request.prefill_s``) over the
requests admitted inside the window, in ms."""

from benchmark import stats


def read(record: dict, args: dict):
    med = stats.median(
        r["prefill_s"] for r in record["requests"]
        if r["prefill_s"] is not None
        and stats.in_window(r["prefill_start_t"], record["t_open"], record["t_close"])
    )
    return None if med is None else med * 1e3
