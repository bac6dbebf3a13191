"""1 - (union of operation intervals on a chip's plane / traced slice),
mean over the cell's chips, from the profiler trace."""


def read(record: dict, args: dict):
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
