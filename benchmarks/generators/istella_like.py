"""Istella-LETOR-shaped ranking data: dense float32 features, graded
labels 0-4 by global quantiles of a noisy linear relevance (the cuts of
generators/msltr_like.py), and queries of uneven length: a log-normal
with mean ``rows / queries`` and log-sd ``docs_per_query_log_sd``,
clipped to 1..``docs_per_query_max`` and adjusted so that the lengths sum
to ``rows`` exactly.

Rows, lengths and the relevance's weights are one fixed draw
(``POPULATION``); the seed decides only the order the *queries* come in,
and a query's documents stay together and in their order (PERF.md,
section 4: a new sample for every seed moves an iteration by 0.7-1%
through the trees it grows). Features are half-normal, so that bin 0
holds 0.0 whatever the draw (generators/higgs_like.py says what a signed
feature costs).

Made on the device ``FEATURES_PER_CHUNK`` features at a time and copied
to the host chunk by chunk: the process's device peak has to be the
program's, and a whole [220, 7.3M] float32 with its reordered copy would
be 12.9 GB. ``XT`` is the matrix transposed, [F, N].
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .higgs_like import HALF_NORMAL_MEAN, HALF_NORMAL_STD, POPULATION

FEATURES_PER_CHUNK = 10
LABEL_CUTS = (0.55, 0.75, 0.9, 0.97)


def query_lengths(rows, queries, log_sd, cap):
    """[queries] int64 in 1..cap that sum to ``rows``: one fixed draw of
    a log-normal, its scale found by bisection so that the clipped,
    rounded lengths come closest to ``rows``, the remainder spread one
    document at a time over the queries that have room."""
    if not queries <= rows <= queries * cap:
        raise ValueError(f"{rows} rows do not fit {queries} queries of "
                         f"1..{cap} documents")
    z = np.random.default_rng(POPULATION).standard_normal(queries)

    def at(mu):
        return np.clip(np.rint(np.exp(mu + log_sd * z)), 1, cap).astype(
            np.int64)

    lo, hi = -20.0, np.log(cap) + 20.0
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if at(mid).sum() < rows else (lo, mid)
    lengths = at(lo)
    short = rows - int(lengths.sum())
    while short:
        step = 1 if short > 0 else -1
        room = np.flatnonzero(lengths < cap if step > 0 else lengths > 1)
        room = room[:abs(short)]
        lengths[room] += step
        short -= step * len(room)
    return lengths


def row_order(seed, lengths):
    """[rows] int32: the population's rows in the order this seed hands
    them over, query by query in the seed's order of the queries; and the
    lengths in that order."""
    order = np.random.default_rng(seed).permutation(len(lengths))
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    taken = lengths[order]
    first = np.repeat(starts[order] - np.concatenate(
        [[0], np.cumsum(taken)[:-1]]), taken)
    return (first + np.arange(first.size)).astype(np.int32), taken


@functools.partial(jax.jit, static_argnames=("rows",))
def _chunk(keys, w, order, rows):
    """One chunk of features, a key each, in the seed's row order:
    [len(keys), rows] float32, |N(0, 1)|; and its part of the relevance."""
    XT = jax.lax.map(
        lambda k: jnp.abs(jax.random.normal(k, (rows,), jnp.float32))[order],
        keys)
    return XT, w @ XT


@jax.jit
def _labels(rel, noise_key, order):
    noise = jax.random.normal(noise_key, rel.shape)[order]
    rel = rel + 0.8 * noise
    cuts = jnp.quantile(rel, jnp.array(LABEL_CUTS))
    return jnp.searchsorted(cuts, rel, side="right").astype(jnp.float32)


def _to_host(XT, lo, hi, part):
    XT[lo:hi] = np.asarray(part)
    part.delete()


def make(seed, rows, features, queries, docs_per_query_log_sd,
         docs_per_query_max):
    lengths = query_lengths(rows, queries, docs_per_query_log_sd,
                            docs_per_query_max)
    order, group = row_order(seed, lengths)
    order = jnp.asarray(order)
    k_x, k_noise, k_w = jax.random.split(jax.random.key(POPULATION), 3)
    keys = jax.random.split(k_x, features)
    w = jax.random.normal(k_w, (features,)) / np.sqrt(features)
    XT = np.empty((features, rows), np.float32)
    rel = jnp.zeros((rows,), jnp.float32)
    made = []               # a chunk is copied out while the next one runs
    for lo in range(0, features, FEATURES_PER_CHUNK):
        hi = min(lo + FEATURES_PER_CHUNK, features)
        part, rel_part = _chunk(keys[lo:hi], w[lo:hi], order, rows)
        rel = rel + rel_part
        made.append((lo, hi, part))
        if len(made) == 2:
            _to_host(XT, *made.pop(0))
    _to_host(XT, *made.pop(0))
    rel = (rel - HALF_NORMAL_MEAN * jnp.sum(w)) / HALF_NORMAL_STD
    label = np.asarray(_labels(rel, k_noise, order))
    return {"XT": XT, "label": label, "group": group}
