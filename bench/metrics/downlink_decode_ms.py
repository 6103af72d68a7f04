"""The window's mean of the program span ``channel.recv``, the broadcast's
decode at a learner, in ms."""

from bench import spans

SPANS = (spans.METRICS["downlink_decode_ms"],)


def read(ctx):
    return spans.mean_ms(spans.window(ctx.before, ctx.after), SPANS[0])
