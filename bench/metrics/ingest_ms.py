"""Mean host time of one ``Controller.ingest`` call, in ms."""


def read(ctx):
    mean = ctx.spans.mean_s("ingest")
    return None if mean is None else 1e3 * mean
