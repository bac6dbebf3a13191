"""Share of the KV positions a decode pass gathers that are live: the sum
over the window's passes of the active slots' lengths
(``serve.kv_live_positions``, one sample a pass) over what the densified
gather reads whatever is live: ``max_slots x pages_per_seq x page_len`` a
pass, from the cell's ``engine`` sizes."""

from benchmark import ring


def read(record: dict, args: dict):
    live = ring.series(record, "serve.kv_live_positions")
    if not live:
        return None
    eng = record["cell"]["engine"]
    gathered = eng["max_slots"] * eng["pages_per_seq"] * eng["page_len"]
    return 100.0 * sum(v for _, v in live) / (len(live) * gathered)
