"""Share of the window's decode ticks that went to the device before the
program ahead of them was fetched: the mean of the ``serve.tick_ahead``
samples (1 for such a tick, 0 for one dispatched after the fetch, one
sample a tick, stamped at its dispatch), in %.  ``None`` where the program
samples no such ring."""

from benchmark import ring


def read(record: dict, args: dict):
    samples = ring.series(record, "serve.tick_ahead")
    if not samples:
        return None
    return 100.0 * sum(v for _, v in samples) / len(samples)
