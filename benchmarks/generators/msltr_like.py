"""MS-LTR-shaped ranking data: dense float32 features, graded labels 0-4
by global quantiles of a noisy linear relevance, query groups of
``docs_per_query`` documents (the last may be shorter). After
``bench.make_msltr_like``, with half-normal features (MSLR-WEB30K's are
counts, lengths and scores, none below zero; generators/higgs_like.py says
what a signed feature costs) and one fixed draw of the relevance's
weights. Every seed is a new sample of rows here. Made on the device in
one jitted call from the seed."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .higgs_like import POPULATION, half_normal_rows, standardized


@functools.partial(jax.jit, static_argnames=("rows", "features"))
def _make(key, rows, features):
    k_x, k_noise = jax.random.split(key)
    XT = half_normal_rows(k_x, rows, features)
    w = jax.random.normal(jax.random.key(POPULATION),
                          (features,)) / np.sqrt(features)
    rel = standardized(w, XT) + 0.8 * jax.random.normal(k_noise, (rows,))
    cuts = jnp.quantile(rel, jnp.array([0.55, 0.75, 0.9, 0.97]))
    return XT, jnp.searchsorted(cuts, rel, side="right").astype(jnp.float32)


def make(seed, rows, features, docs_per_query):
    XT, label = _make(jax.random.key(seed), rows, features)
    group = np.full(rows // docs_per_query, docs_per_query, np.int64)
    if rows % docs_per_query:
        group = np.append(group, rows % docs_per_query)
    out = {"XT": np.asarray(XT), "label": np.asarray(label), "group": group}
    XT.delete()
    return out
