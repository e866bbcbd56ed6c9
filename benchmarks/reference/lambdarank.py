"""LambdaRank with NDCG (LightGBM ``objective=lambdarank``), as
rank_objective.hpp describes it. Within a query the documents are sorted
by score, descending and stable. Every pair of sorted positions (a, b),
a < b, a < truncation_level, whose labels differ, contributes

    delta = |gain_hi - gain_lo| * |disc_a - disc_b| / maxDCG@truncation
    delta /= 0.01 + |s_hi - s_lo|        (lambdarank_norm, when the query's
                                          best and worst scores differ)
    rho   = 1 / (1 + exp(sigma (s_hi - s_lo)))
    lambda = -sigma rho delta            to the higher-labelled document,
                                         and its negative to the lower
    hess   = sigma^2 rho (1 - rho) delta to both

and, with lambdarank_norm, a query's lambdas and hessians are scaled by
log2(1 + S) / S, S the sum of |lambda| over both documents of its pairs.
gain = 2^label - 1, disc_r = 1 / log2(r + 2).
"""
import jax
import jax.numpy as jnp
import numpy as np

QUERIES_PER_BLOCK = 128


def prepare(label, group, params):
    group = np.asarray(group, np.int64)
    starts = np.concatenate([[0], np.cumsum(group)[:-1]])
    m = int(group.max())
    q = len(group)
    pad_q = (-q) % QUERIES_PER_BLOCK
    idx = starts[:, None] + np.arange(m)[None, :]
    valid = np.arange(m)[None, :] < group[:, None]
    idx = np.where(valid, idx, 0)
    idx = np.pad(idx, ((0, pad_q), (0, 0)))
    valid = np.pad(valid, ((0, pad_q), (0, 0)))
    trunc = int(params.get("lambdarank_truncation_level", 30))
    gain = 2.0 ** np.asarray(label, np.float64) - 1.0
    gq = np.where(valid, gain[idx], 0.0)
    top = -np.sort(-gq, axis=1)[:, :trunc]
    max_dcg = (top / np.log2(np.arange(top.shape[1]) + 2.0)).sum(axis=1)
    inv_max_dcg = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, 1e-300), 0)
    # where each row sits in the padded [Q, M] layout, to read results back
    row_q = np.repeat(np.arange(q), group)
    row_flat = row_q * m + (np.arange(len(label)) - starts[row_q])
    return {"idx": jnp.asarray(idx, jnp.int32), "valid": jnp.asarray(valid),
            "row_flat": jnp.asarray(row_flat, jnp.int32),
            "gain": jnp.asarray(gq, jnp.float32),
            "inv_max_dcg": jnp.asarray(inv_max_dcg, jnp.float32),
            "trunc": min(trunc, m),
            "sigma": float(params.get("sigmoid", 1.0)),
            "norm": bool(params.get("lambdarank_norm", True))}


def _block(state, s, gain, valid, inv_max_dcg, dtype):
    """One block of queries, [Q, M] each, in document order."""
    sigma, t = state["sigma"], state["trunc"]
    m = s.shape[1]
    s = jnp.where(valid, s, -jnp.inf)
    order = jnp.argsort(-s, axis=1, stable=True)
    s = jnp.take_along_axis(s, order, axis=1).astype(dtype)
    gain = jnp.take_along_axis(gain, order, axis=1).astype(dtype)
    valid = jnp.take_along_axis(valid, order, axis=1)
    disc = (1.0 / jnp.log2(jnp.arange(m, dtype=jnp.float32) + 2.0)
            ).astype(dtype)
    a, b = jnp.arange(t)[:, None], jnp.arange(m)[None, :]
    pair = ((a < b)[None] & valid[:, :t, None] & valid[:, None, :]
            & (gain[:, :t, None] != gain[:, None, :]))
    a_high = gain[:, :t, None] > gain[:, None, :]
    ds = s[:, :t, None] - s[:, None, :]
    ds = jnp.where(pair, jnp.where(a_high, ds, -ds), 0)      # s_hi - s_lo
    delta = (jnp.abs(gain[:, :t, None] - gain[:, None, :])
             * jnp.abs(disc[None, :t, None] - disc[None, None, :])
             * inv_max_dcg[:, None, None].astype(dtype))
    if state["norm"]:
        n_valid = jnp.sum(valid, axis=1)
        worst = jnp.take_along_axis(
            s, jnp.maximum(n_valid - 1, 0)[:, None], axis=1)[:, 0]
        spread = (s[:, 0] != worst)[:, None, None]
        delta = jnp.where(spread, delta / (0.01 + jnp.abs(ds)), delta)
    rho = jax.nn.sigmoid(-sigma * ds)
    lam = jnp.where(pair, -sigma * rho * delta, 0)       # to the higher one
    hess = jnp.where(pair, sigma * sigma * rho * (1 - rho) * delta, 0)
    lam_a = jnp.where(a_high, lam, -lam)                 # to position a
    rest = ((0, 0), (0, m - t))
    g = jnp.pad(lam_a.sum(axis=2), rest) - lam_a.sum(axis=1)
    h = jnp.pad(hess.sum(axis=2), rest) + hess.sum(axis=1)
    if state["norm"]:
        total = -2.0 * lam.astype(jnp.float32).sum(axis=(1, 2))
        scale = jnp.where(total > 0, jnp.log2(1 + total)
                          / jnp.maximum(total, 1e-30), 1.0)
        g, h = g * scale[:, None].astype(dtype), h * scale[:, None].astype(
            dtype)
    back = jnp.argsort(order, axis=1)
    return (jnp.take_along_axis(g, back, axis=1).astype(jnp.float32),
            jnp.take_along_axis(h, back, axis=1).astype(jnp.float32))


def gradients(state, score, dtype=jnp.float32):
    idx, valid = state["idx"], state["valid"]
    m = idx.shape[1]
    blocks = idx.shape[0] // QUERIES_PER_BLOCK
    shape = (blocks, QUERIES_PER_BLOCK, m)
    g, h = jax.lax.map(
        lambda x: _block(state, *x, dtype),
        (score[idx].reshape(shape), state["gain"].reshape(shape),
         valid.reshape(shape),
         state["inv_max_dcg"].reshape(blocks, QUERIES_PER_BLOCK)))
    return g.reshape(-1)[state["row_flat"]], h.reshape(-1)[state["row_flat"]]
