"""device_idle.stage2: share of the traced Stage-II window in which no
operation ran on the chip, in %: 1 - (union of the device-op intervals
over the window's length)."""


def read(ctx):
    if not ctx.get("window_s") or ctx.get("kind") != "stage2":
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
