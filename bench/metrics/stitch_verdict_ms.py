"""Stitch and verdict: milliseconds from the last round's return to
``BatteryRun.result()`` returning (the ``stitch`` spans), per verdict."""


def read(ctx):
    spans = ctx.trace.spans("stitch")
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6
