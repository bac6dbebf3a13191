"""Device time of the Pallas flash-attention kernels (forward, dq, dkv)
per training step: the self time of every trace operation whose name
holds one of ``args.match``, over the traced steps, in ms."""

from benchmark import trace_reduce


def read(record: dict, args: dict):
    if not record.get("trace") or not record["flash"]["used"]:
        return None
    sec = trace_reduce.op_seconds(record["trace"], args["match"])
    return sec / record["trace_steps"] * 1e3 if sec > 0 else None
