"""The window's share of the cell's chips' bf16 peak, in %.

Training and evaluation operations of every learner task in the window
(the family's ``learner_flops``, from ``bench/counts.py``) over the
window's host-clock length times the peak of ``bench/peaks.json``, once
for each chip the cell holds.
"""


def read(ctx):
    peak = ctx.w.chips * ctx.peak("bf16_flops_per_s")
    return 100.0 * ctx.flops() / (ctx.window_s * peak)
