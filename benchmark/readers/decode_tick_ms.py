"""Median wall of one decode tick: the ``eng.tick_wall_s`` samples taken
inside the window, in ms.  The engine keeps them in insertion order under
a cap of 4,096; past it they are a reservoir and this reads nothing."""

from benchmark import stats


def read(record: dict, args: dict):
    if not record.get("tick_s"):
        return None
    return stats.median(record["tick_s"]) * 1e3
