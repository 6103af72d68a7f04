"""The round's reduction against its roofline, in %.

Device time of the reduce program in the trace (``masked_weighted_average``
for an f32 arena, ``masked_fedavg_q8`` for an int8 one), against the larger
of its bytes over HBM bandwidth and its operations over peak, from the
arena's shape (``bench/counts.reduce_cost``).  A column-sharded arena is
reduced shard by shard, one execution on each device: each is held to its
own shard's roofline.  Nothing when the trace holds no reduce.
"""

from bench import counts

PROGRAMS = ("masked_weighted_average", "masked_fedavg_q8")


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.program_time(*PROGRAMS)
    if not calls:
        return None
    s = ctx.shapes
    best = counts.roofline_s(counts.reduce_cost(s.rows, s.shard_width, s.arena_dtype),
                             {k: ctx.peak(k) for k in ("hbm_bytes_per_s", "bf16_flops_per_s")})
    return 100.0 * best * calls / secs
