"""A number the runner measured directly, by key (``args.key``), scaled
by ``args.scale`` (default 1)."""


def read(record: dict, args: dict):
    value = record.get(args["key"])
    return None if value is None else value * args.get("scale", 1.0)
