"""Test kernels: the Pallas ``gf2_rank`` kernel's share of its roofline
(percent; bytes-bound, see ``bench/roofline.py``), over its calls on the
pool's devices in the traced block."""
from bench.roofline import roofline_share


def read(ctx):
    lo, hi = ctx.trace.window
    events = [ev for d in ctx.device_ids() for ev in ctx.trace.devices[d]
              if ev[1] >= lo and ev[2] <= hi]
    return roofline_share("gf2_rank", events, ctx.peaks["hbm_bytes_per_s"])
