"""``args.q``-th percentile, over the requests sent in the window and
admitted, of the time from arrival to the start of the prefill pass that
admitted them (``prefill_start_t - arrival_t``: the engine's own stamps),
in ms."""

from benchmark import stats


def read(record: dict, args: dict):
    return stats.percentile(
        ((r["prefill_start_t"] - r["arrival_t"]) * 1e3
         for r in record["requests"]
         if r["sent_in_window"] and r["prefill_start_t"] is not None),
        args["q"],
    )
