"""Mean ``RoundTimings.train_dispatch_s`` of the window's rounds, in ms."""


def read(ctx):
    return 1e3 * sum(t.train_dispatch_s for t in ctx.timings) / len(ctx.timings)
