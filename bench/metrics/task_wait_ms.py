"""The window's mean of the program span ``engine.task.wait``, a learner task's
wait from its submission to a device slot, in ms."""

from bench import spans

SPANS = (spans.METRICS["task_wait_ms"],)


def read(ctx):
    return spans.mean_ms(spans.window(ctx.before, ctx.after), SPANS[0])
