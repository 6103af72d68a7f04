"""The window's mean of the program span ``controller.commit``, the server step
and model swap, which wait for the reduce on the device, in ms."""

from bench import spans

SPANS = (spans.METRICS["commit_ms"],)


def read(ctx):
    return spans.mean_ms(spans.window(ctx.before, ctx.after), SPANS[0])
