"""build_s: seconds of the engine's preprocessing in set-up.

Benchmark clock around ``degree_and_densify`` -> ``build_dsss``, and for
the disk tier ``write_dsss`` -> ``GraphSession.open`` too.
"""


def read(run):
    return run.build_s
