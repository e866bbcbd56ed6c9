"""Higgs-shaped data: dense float32 features and a nonlinear binary
target, after ``bench.make_higgs_like`` (same target), with three changes
that PERF.md (Findings, PR 27) gives the readings for. The features are
half-normal, |N(0, 1)|, so that the bin that holds 0.0 is bin 0 whatever
the draw: the program compiles that bin's index into its step, and a data
set that moves it costs a compile of 100 s. The rows are one fixed draw
(``POPULATION``), and the seed decides the order they come in: a new
sample for every seed grew other trees, and an iteration's time followed
the trees by 0.7% from seed to seed where one seed's two runs agree to
0.02%. With the order alone from the seed the program still sees another
data set (its bin boundaries come from other sampled rows), and the work
of an iteration stays alike. Made on the device in one jitted call,
feature by feature so that the temporaries stay small, and handed back as
host arrays: the program's ``Dataset`` takes host data. ``XT`` is the
matrix transposed, [F, N]."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

POPULATION = 0                          # the key of the fixed draw
HALF_NORMAL_MEAN = np.sqrt(2 / np.pi)
HALF_NORMAL_STD = np.sqrt(1 - 2 / np.pi)


def half_normal_rows(key, rows, features):
    """[features, rows] float32, |N(0, 1)|, one feature at a time."""
    return jax.lax.map(
        lambda k: jnp.abs(jax.random.normal(k, (rows,), jnp.float32)),
        jax.random.split(key, features))


def standardized(w, XT):
    """w @ Z for Z the features brought to mean 0 and variance 1."""
    return (w @ XT - HALF_NORMAL_MEAN * jnp.sum(w)) / HALF_NORMAL_STD


@functools.partial(jax.jit, static_argnames=("rows", "features"))
def _make(order_key, rows, features):
    k_x, k_noise, k_w1, k_w2 = jax.random.split(
        jax.random.key(POPULATION), 4)
    XT = half_normal_rows(k_x, rows, features)
    w1 = jax.random.normal(k_w1, (features,)) / np.sqrt(features)
    w2 = jax.random.normal(k_w2, (features,)) / np.sqrt(features)
    logits = (standardized(w1, XT) + 0.7 * jnp.abs(standardized(w2, XT))
              - 0.4 + 0.5 * jax.random.normal(k_noise, (rows,)))
    order = jax.random.permutation(order_key, rows)
    label = (logits > 0).astype(jnp.float32)
    return jax.lax.map(lambda x: x[order], XT), label[order]


def make(seed, rows, features):
    XT, label = _make(jax.random.key(seed), rows, features)
    out = {"XT": np.asarray(XT), "label": np.asarray(label), "group": None}
    XT.delete()
    return out
