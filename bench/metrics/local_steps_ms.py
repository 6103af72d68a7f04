"""The window's mean of the program span ``learner.steps``, a learner's local
steps, in ms."""

from bench import spans

SPANS = (spans.METRICS["local_steps_ms"],)


def read(ctx):
    return spans.mean_ms(spans.window(ctx.before, ctx.after), SPANS[0])
