"""compile_s: seconds of JAX backend compiles (cache loads included) in set-up.

Summed from ``jax.monitoring`` compile events from process start to the
first timed job.
"""


def read(run):
    return run.compile_s
