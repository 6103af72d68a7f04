"""The window's mean of the program span ``controller.screen``, the admission
screen's blocking read of the upload's norm, in ms."""

from bench import spans

SPANS = (spans.METRICS["screen_sync_ms"],)


def read(ctx):
    return spans.mean_ms(spans.window(ctx.before, ctx.after), SPANS[0])
