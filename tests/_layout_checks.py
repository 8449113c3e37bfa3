"""Shared PackedSweep layout invariants.

One implementation of the tile-layout contract, used by the deterministic
suite (tests/test_packed_sweep.py) and the hypothesis property suite
(tests/test_packed_tiling_property.py) so the two cannot drift when the
schema changes.
"""
import numpy as np


def check_layout(g, packed):
    """Assert every invariant any packing mode must satisfy.

    * Exact coverage: the tiles' real edges are the flat DSSS edge stream,
      in stream order (``row_offset`` partitions ``[0, m)``).
    * Run integrity: global hub slots partition tile-contiguously — no
      (sub-shard, destination) run is ever split across tiles — and
      ``run_local`` reproduces the windowed global slots.
    * ``run_dst`` maps every real run to its global destination and every
      padded slot to the ``n_pad`` drop sentinel.
    * Per-tile interval metadata matches the first edge.
    * Where the run fold applies, ``run_end`` holds each run's last real
      slot, increasing over the tile's ``u`` runs and 0 past them.
    """
    e_valid = packed.e_valid
    srcs = np.concatenate(
        [packed.src[t, :e] for t, e in enumerate(e_valid)]
    ) if packed.num_tiles else np.zeros(0, np.int32)
    dsts = np.concatenate(
        [packed.dst[t, :e] for t, e in enumerate(e_valid)]
    ) if packed.num_tiles else np.zeros(0, np.int32)
    np.testing.assert_array_equal(srcs, g.src)
    np.testing.assert_array_equal(dsts, g.dst)
    if packed.weights is not None:
        ws = np.concatenate([packed.weights[t, :e] for t, e in enumerate(e_valid)])
        np.testing.assert_array_equal(ws, g.weights)
    assert int(e_valid.sum()) == g.m == packed.m
    np.testing.assert_array_equal(
        packed.row_offset, np.concatenate([[0], np.cumsum(e_valid)[:-1]])
    )
    np.testing.assert_array_equal(
        packed.base_slot[1:], packed.base_slot[:-1] + packed.u[:-1]
    )
    if packed.num_tiles:
        assert packed.base_slot[0] == 0
        assert packed.base_slot[-1] + packed.u[-1] == g.hub_offsets[-1, -1]
    gslot = g.global_hub_slots()
    isz = g.interval_size
    for t, e in enumerate(e_valid):
        lo = packed.row_offset[t]
        np.testing.assert_array_equal(
            packed.run_local[t, :e].astype(np.int64) + packed.base_slot[t],
            gslot[lo : lo + e],
        )
        assert packed.run_local[t, :e].max(initial=0) < packed.u[t]
        np.testing.assert_array_equal(
            packed.run_dst[t, packed.run_local[t, :e]], packed.dst[t, :e]
        )
        assert (packed.run_dst[t, packed.u[t] :] == g.n_pad).all()
        i, j = packed.src_interval[t], packed.dst_interval[t]
        assert i == packed.src[t, 0] // isz and j == packed.dst[t, 0] // isz
    check_run_end(packed)


def check_run_end(packed):
    """``run_end`` is ``None`` or each run's last real slot (0 past ``u``),
    ``R`` a multiple of 128 with ``max(u) ≤ R`` and ``2·R ≤ T``."""
    run_end = packed.run_end
    if run_end is None:
        return
    R = run_end.shape[1]
    assert run_end.shape == (packed.num_tiles, R) and run_end.dtype == np.int32
    assert R % 128 == 0 and packed.u.max() <= R < packed.u.max() + 128
    assert 2 * R <= packed.tile_edges
    for t, (e, u) in enumerate(zip(packed.e_valid, packed.u)):
        rl = packed.run_local[t, :e]
        assert (np.diff(rl) >= 0).all()
        last = np.full(u, -1)
        np.maximum.at(last, rl, np.arange(e))
        np.testing.assert_array_equal(run_end[t, :u], last)
        assert (np.diff(run_end[t, :u]) > 0).all()
        assert (run_end[t, u:] == 0).all()
