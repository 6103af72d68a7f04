"""Mean host time of one ``Learner.fit`` call (recv excluded), in ms."""


def read(ctx):
    mean = ctx.spans.mean_s("fit")
    return None if mean is None else 1e3 * mean
