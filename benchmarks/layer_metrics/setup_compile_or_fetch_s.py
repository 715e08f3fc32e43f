"""Seconds of set-up in the backend's compile of the trainer's programs, or
in their fetch from the persistent cache: the program's `jax.compile` events
that end before the window starts and whose parent span is the trainer's
(`train....`)."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.trainer_setup(ctx, "jax.compile")
    if not evs:
        return None
    return hostspans.seconds(evs, "jax.compile")
