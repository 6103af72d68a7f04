"""The uplink encode's rate over the window, in GB/s.

Payload bytes the learners' ``Channel.upload`` produced
(``channel.upload_bytes``) over the seconds its encode took, the row's
device-to-host copy included (``channel.upload_serialize_s``, the
``channel.upload`` span's sum), both counted by the program.
"""

from bench import spans

COUNTERS = ("channel.upload_bytes", "channel.upload_serialize_s")


def read(ctx):
    nbytes, secs = (spans.delta(ctx.before, ctx.after, name) for name in COUNTERS)
    if not nbytes or not secs:
        return None
    return nbytes / secs / 1e9
