"""Idle share of the chip in the open-loop cells: 1 - (union of the
device's operation intervals / traced window).  Moves ``ttft_p90_ms``."""


def read(ctx):
    w = ctx.red.window_s
    if w <= 0:
        return None
    return (1.0 - ctx.red.busy_s() / w) * 100.0
