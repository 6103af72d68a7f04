"""Mean ``RoundTimings.eval_round_s`` (the evaluation fan-out) per round, in ms."""


def read(ctx):
    return 1e3 * sum(t.eval_round_s for t in ctx.timings) / len(ctx.timings)
