"""The fold contract between execution backends.

The ``packed`` scan folds a tile in one of two orders. Its run fold
(packings whose runs are contiguous and few, the DSSS power-law case)
sums each destination run as a tree (a segmented scan), then adds the
run partials left to right across runs and tiles in stream order. Its
per-edge fold adds each edge's contribution straight into the
accumulator at its destination, left to right in tile order. The
``per_block`` executor first reduces a destination's edges into run
partials, then folds the partials. Min and max are order-free, so those
programs agree bit for bit under every fold. A float32 sum is
reassociated, so PageRank and PPR agree to float32 rounding over a
destination's in-edges: within ``SUM_RTOL`` relative, plus ``SUM_ATOL``
absolute for ranks near zero, where a relative bound means nothing.
Comparisons of one backend with itself (residency tiers, chunking,
selective against full scans, resume) stay bitwise.
"""
import numpy as np

SUM_RTOL = 1e-5
SUM_ATOL = 1e-8


def assert_attrs_match(expected, actual, reduce: str):
    """Bitwise for min/max programs; within the stated tolerance for sums."""
    if reduce == "sum":
        np.testing.assert_allclose(
            actual, expected, rtol=SUM_RTOL, atol=SUM_ATOL
        )
    else:
        np.testing.assert_array_equal(actual, expected)
