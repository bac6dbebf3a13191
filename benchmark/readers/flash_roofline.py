"""The flash kernels' share of their roofline: the least time one chip
could take for its attention calls of one step (forward and backward; for
each the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
which at ctx 2,048 and head_dim 128 is the compute bound) over their
measured device time.  The operations and bytes of a call are the family's
count, in the record.  Only the calls the algorithm needs count: the kernel
also runs in the pipeline's bubble ticks, which lowers the share."""

from benchmark import flops, trace_reduce


def read(record: dict, args: dict):
    if not record.get("trace") or not record["flash"]["used"] or not record.get("peaks"):
        return None
    sec = trace_reduce.op_seconds(record["trace"], args["match"]) / record["trace_steps"]
    if sec <= 0:
        return None
    f = record["flash"]
    least = sum(
        flops.roofline_seconds(*f[direction], record["peaks"])[0]
        for direction in ("forward", "backward")
    )
    return 100.0 * least * f["calls_per_step"] / record["chips"] / sec
