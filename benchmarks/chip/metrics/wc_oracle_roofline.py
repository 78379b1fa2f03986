"""wc_oracle_roofline: device time of the WC oracle's trip kernel (the
``pallas_call`` that ``_wc_step`` wraps) against the least time the chip
needs for the work its trips require, in %.

Per trip and episode the algorithm pops the lexicographic minimum
completion over R = nd + nd^2 resources (four chained masked minima and
the first matching lane: 5R operations) and writes up to K candidate rows
of six columns (6K).  Its compulsory HBM traffic is the candidate rows
and their targets in (4 x 7K bytes) and the popped slot and time out
(8 bytes); the running table is the trip loop's own state, kept across
trips, and not counted.  Until the program names a scope around the
oracle, its time is these kernel events alone.
"""

KERNEL = "_wc_step"      # the jitted function around the kernel


def trip_counts(s: dict) -> tuple[float, float]:
    """(operations, bytes) of one kernel call: one trip of a batch."""
    B, R, K = s["oracle_batch"], s["oracle_R"], s["oracle_K"]
    return B * (5 * R + 6 * K), B * (4 * 7 * K + 8)


def read(ctx):
    if ctx.get("kind") != "stage2":
        return None
    evs = [e for c in ctx["events"].values()
           for e in ctx["pallas_calls"](c, KERNEL)]
    if not evs:
        return None
    ops, nbytes = trip_counts(ctx["shapes"])
    pk = ctx["peaks"]
    least = len(evs) * max(ops / pk["flops_per_s"],
                           nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e - s for _, s, e in evs) / 1e9)
