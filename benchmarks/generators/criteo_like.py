"""Click-log-shaped data after LightGBM's parallel experiment (Criteo, 13
integer and 26 categorical features, the categorical ones replaced by
their click-through rate and their count: 67 dense columns) and a rare
binary target. The source gives the total and not each column's kind, so
the kinds are assumed (the configuration's file says so):

    columns  0..12   integer-valued counts with many ties (floor of a
                     log-normal: a third of the rows read 0)
    columns 13..38   rates in (0, 1), most of them small
    columns 39..66   heavy-tailed counts (26 beside the rates, 2 more)

Every column is a monotone function of a standard normal of its own, the
target a nonlinear function of a few of those normals and of noise; every
value is non-negative (the bin that holds 0.0 is bin 0 whatever the draw:
``higgs_like`` says why) and none is missing.

The rows are one fixed draw, made ``BLOCK`` rows at a time: block ``b`` of
the population is a function of ``b`` alone, whatever the seed. The seed
decides the order the blocks come in, and with it the shard each row lies
in under ``tree_learner=data``. No gather and no array of all rows is on a
device: a call makes ``GROUP`` blocks on one device (0.27 GB of output,
twice that while it runs), the calls go round the local devices, two in
flight on each, and each result is copied into the host's ``XT`` [F, N]
as it arrives.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

POPULATION = 0          # the key of the fixed draw
BLOCK = 4000            # rows a population block holds
GROUP = 250             # blocks a device makes in one call
INTEGER, RATES = 13, 26  # columns of the first two kinds; the rest count
POSITIVE_SHIFT = -2.7   # with the terms below: 3.46% of the labels positive


def columns(z):
    """[F, R] standard normals to the columns' values, kind by kind; the
    location and scale move a little from column to column."""
    f = z.shape[0]
    step = jnp.linspace(0.0, 1.0, f)[:, None]
    kind = jnp.arange(f)[:, None]
    integer = jnp.floor(jnp.exp((1.0 + 0.5 * step) * z + 0.5))
    rate = jax.nn.sigmoid((0.8 + 0.6 * step) * z - 3.0 + step)
    count = jnp.floor(jnp.exp((1.5 + step) * z + 3.0))
    return jnp.where(kind < INTEGER, integer,
                     jnp.where(kind < INTEGER + RATES, rate, count))


def target(z, noise):
    """The label from a few columns' normals: two rates, a count, an
    integer column, one product and one absolute value."""
    r0, r1, c0, i0 = z[INTEGER], z[INTEGER + 1], z[INTEGER + RATES], z[0]
    logit = (POSITIVE_SHIFT + 0.9 * r0 + 0.6 * jnp.abs(r1) + 0.5 * c0 * i0
             - 0.4 * jnp.maximum(c0, 0.0) + 0.7 * noise)
    return (logit > 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("features",))
def _blocks(ids, features):
    """Population blocks ``ids`` [G] side by side: ([F, G * BLOCK]
    float32, [G * BLOCK] labels)."""
    def one(block_id):
        k_z, k_n = jax.random.split(jax.random.fold_in(
            jax.random.key(POPULATION), block_id))
        z = jax.random.normal(k_z, (features, BLOCK), jnp.float32)
        noise = jax.random.normal(k_n, (BLOCK,), jnp.float32)
        return columns(z), target(z, noise)

    x, y = jax.lax.map(one, ids)                    # [G, F, R], [G, R]
    return jnp.moveaxis(x, 0, 1).reshape(features, -1), y.reshape(-1)


def make(seed, rows, features):
    n_blocks = -(-rows // BLOCK)
    order = np.random.default_rng(seed).permutation(n_blocks)
    # the population's last block may be short: it keeps its rows' count
    # wherever the seed puts it
    length = np.full(n_blocks, BLOCK)
    length[n_blocks - 1] = rows - (n_blocks - 1) * BLOCK
    starts = np.concatenate([[0], np.cumsum(length[order])])
    XT = np.empty((features, rows), np.float32)
    label = np.empty((rows,), np.float32)
    devices = jax.local_devices()
    group = min(GROUP, n_blocks)

    def keep(lo, ids, x, y):
        """A call's blocks lie side by side in the output too: one copy,
        but for the call that holds the short block."""
        x, y = np.asarray(x), np.asarray(y)
        if (length[ids] == BLOCK).all():
            at, n = starts[lo], len(ids) * BLOCK
            XT[:, at:at + n], label[at:at + n] = x[:, :n], y[:n]
            return
        for i, b in enumerate(ids):
            at, n = starts[lo + i], length[b]
            XT[:, at:at + n] = x[:, i * BLOCK:i * BLOCK + n]
            label[at:at + n] = y[i * BLOCK:i * BLOCK + n]

    pending = []            # at most two calls a device, oldest first
    for c, lo in enumerate(range(0, n_blocks, group)):
        ids = order[lo:lo + group]
        padded = np.zeros(group, np.int32)
        padded[:len(ids)] = ids
        if len(pending) == 2 * len(devices):
            keep(*pending.pop(0))
        pending.append((lo, ids, *_blocks(
            jax.device_put(padded, devices[c % len(devices)]), features)))
    for call in pending:
        keep(*call)
    return {"XT": XT, "label": label, "group": None}
