"""Graph500 Kronecker (R-MAT) graphs, drawn on the device from a seed.

The generator a configuration names with ``"generator": "graph500"``; it
reads ``scale``, ``edge_factor``, ``initiator`` and ``undirected``.

Follows the Graph500 reference generator (``kronecker_generator.m`` of the
Graph500 specification): for each of ``scale`` bit levels every edge draws
its source bit with P(1) = C + D and its destination bit with
P(1) = D / (C + D) under a set source bit, B / (A + B) otherwise; the
vertex labels are then permuted at random so that hubs do not cluster in
low ids. The permutation is a keyed bijection of ``[0, 2**scale)`` drawn
from the seed (rounds of an odd multiply, an add and an xor-shift, each
one-to-one modulo ``2**scale``), so it runs element by element on the
device: a sort-based shuffle of 2**23 labels takes the TPU compiler
20-40 s. The spec's final shuffle of the edge list is left out: the
engine's degreeing pass sorts and de-duplicates edges, so their order
never reaches it.

The graph is drawn on the default device by one jitted program, in chunks
of at most ``CHUNK_EDGES`` edges, each copied to the host before the next
is drawn, so that the generator's device memory stays far below the
engine's (``memory_peak_bytes`` is the process's peak). The result is two
host int32 arrays of raw labels in ``[0, 2**scale)``, with self loops and
duplicates left in, as the spec emits them. The spec's graph is undirected:
with ``undirected`` each edge is handed on as two arcs, one each way, as a
directed engine must be given it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["edges", "seed_key", "kronecker_edges"]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any integer seed, all 64 bits of it.

    ``jax.random.key`` keeps only the low 32 bits of a seed when 64-bit
    mode is off, so the high word is folded in separately. ``stream``
    separates independent uses of one seed (graph, job choices).
    """
    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


CHUNK_EDGES = 1 << 22


@functools.partial(jax.jit, static_argnames=("scale", "m", "abc"))
def _kronecker(key, chunk, *, scale: int, m: int, abc: tuple):
    """Edges ``[chunk * m, (chunk + 1) * m)`` of the graph that ``key`` draws."""
    a, b, c = abc
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_perm = jax.random.split(key)
    k_bits = jax.random.fold_in(k_bits, chunk)

    def level(bit, carry):
        src, dst = carry
        k1, k2 = jax.random.split(jax.random.fold_in(k_bits, bit))
        src_bit = jax.random.uniform(k1, (m,)) > ab
        thresh = jnp.where(src_bit, c_norm, a_norm)
        dst_bit = jax.random.uniform(k2, (m,)) > thresh
        src = src | (src_bit.astype(jnp.int32) << bit)
        dst = dst | (dst_bit.astype(jnp.int32) << bit)
        return src, dst

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    return _permute(k_perm, src, scale), _permute(k_perm, dst, scale)


PERMUTE_ROUNDS = 4


def _permute(key, labels, bits: int):
    """A keyed permutation of ``[0, 2**bits)``, applied to each label."""
    mask = jnp.uint32((1 << bits) - 1)
    k_mul, k_add = jax.random.split(key)
    mul = jax.random.bits(k_mul, (PERMUTE_ROUNDS,), jnp.uint32) | jnp.uint32(1)
    add = jax.random.bits(k_add, (PERMUTE_ROUNDS,), jnp.uint32)
    x = labels.astype(jnp.uint32)
    for r in range(PERMUTE_ROUNDS):
        x = (x * mul[r] + add[r]) & mask
        x = x ^ (x >> ((bits + 1) // 2))
    return x.astype(jnp.int32)


def kronecker_edges(
    seed: int, scale: int, edge_factor: int, initiator: tuple[float, float, float]
) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` host int32 arrays of ``edge_factor * 2**scale`` edges."""
    if not 1 <= scale <= 30:
        raise ValueError(f"scale must be in [1, 30], got {scale}")
    a, b, c = (float(x) for x in initiator)
    if min(a, b, c) <= 0 or a + b + c >= 1:
        raise ValueError(f"initiator A, B, C must be positive with sum < 1: {initiator}")
    m = edge_factor << scale
    per = min(m, CHUNK_EDGES)
    key = seed_key(seed)
    src, dst = [], []
    for chunk in range(-(-m // per)):
        s, d = _kronecker(key, chunk, scale=scale, m=per, abc=(a, b, c))
        src.append(np.asarray(s))
        dst.append(np.asarray(d))
    return np.concatenate(src)[:m], np.concatenate(dst)[:m]


def edges(seed: int, config: dict) -> tuple[np.ndarray, np.ndarray, int]:
    """The configuration's graph as ``(src, dst, num_labels)`` host arcs."""
    scale = int(config["scale"])
    src, dst = kronecker_edges(
        seed, scale, int(config["edge_factor"]), tuple(config["initiator"])
    )
    if config["undirected"]:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    return src, dst, 1 << scale
