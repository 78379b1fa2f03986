"""gnn_mp_roofline: device time of the encoder's aggregation kernel (the
``pallas_call`` that ``_segment_sum_mp`` wraps, also under
differentiation) against the least time the chip needs for the segment
sum it computes, in %.

One call sums m edge messages of width d into n node sums: m x d adds.
Its compulsory HBM traffic is the messages in (4md bytes), the node sums
out (4nd) and the segment ids in (4m).  The one-hot assignment grid the
kernel multiplies today is not useful work and is not counted.
"""

KERNEL = "_segment_sum_mp"      # the jitted function around the kernel


def call_counts(s: dict) -> tuple[float, float]:
    """(operations, bytes) of one aggregation call."""
    n, m, d = s["n"], s["m"], s["policy"]["d_hidden"]
    return m * d, 4 * (m * d + n * d + m)


def read(ctx):
    if ctx.get("kind") != "stage2":
        return None
    evs = [e for c in ctx["events"].values()
           for e in ctx["pallas_calls"](c, KERNEL)]
    if not evs:
        return None
    ops, nbytes = call_counts(ctx["shapes"])
    pk = ctx["peaks"]
    least = len(evs) * max(ops / pk["flops_per_s"],
                           nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e - s for _, s, e in evs) / 1e9)
