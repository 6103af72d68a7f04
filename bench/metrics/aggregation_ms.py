"""Mean ``RoundTimings.aggregation_s`` (reduce plus commit) per round, in ms."""


def read(ctx):
    return 1e3 * sum(t.aggregation_s for t in ctx.timings) / len(ctx.timings)
