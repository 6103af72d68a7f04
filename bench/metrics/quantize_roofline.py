"""The int8 uplink's quantize kernel against its roofline, in %.

Device time of the ``quantize`` program (the Pallas kernel of
``kernels/quantize.py`` behind ``kernels.ops.quantize``) in the trace,
against one f32 row's bytes in and int8 values and scales out
(``bench/counts.quantize_cost``) over HBM bandwidth.  Nothing when the trace
holds no quantize call.
"""

from bench import counts

PROGRAMS = ("jit_quantize",)


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.program_time(*PROGRAMS)
    if not calls:
        return None
    best = counts.roofline_s(counts.quantize_cost(ctx.shapes.width),
                             {k: ctx.peak(k) for k in ("hbm_bytes_per_s", "bf16_flops_per_s")})
    return 100.0 * best * calls / secs
