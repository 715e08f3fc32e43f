"""Seconds of set-up that jax spent tracing and lowering the trainer's
programs: the program's `jax.trace` and `jax.lower` events that end before
the window starts and whose parent span is the trainer's (`train....`), so
the benchmark's data program and the reference's are left out. jax's events
nest (a function traced inside the program's trace fires its own), as they do
in the set-up split `run.py` prints."""

from benchmarks.lib import hostspans


def read(ctx):
    evs = hostspans.trainer_setup(ctx, "jax.")
    if not evs:
        return None
    return (hostspans.seconds(evs, "jax.trace")
            + hostspans.seconds(evs, "jax.lower"))
