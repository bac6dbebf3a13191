"""Share of the chip's busy time that the grouped expert products take:
self time of the operations whose name holds ``moe_gmm`` (the kernel's
``pallas_call(name=)``; three calls a layer) over the traced slice's busy
time.  Silent where the trace has no such operation (a program from before
the kernel, or a model without routed experts)."""

from benchmark import trace_reduce


def read(record: dict, args: dict):
    tr = record.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    sec = trace_reduce.op_seconds(tr, args["match"])
    return 100.0 * sec / tr["busy_s"] if sec > 0 else None
