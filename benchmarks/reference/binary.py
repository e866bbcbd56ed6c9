"""Binary log-loss (LightGBM ``objective=binary``): p = sigmoid(sigma s),
g = sigma (p - y), h = sigma^2 p (1 - p)."""
import jax
import jax.numpy as jnp


def prepare(label, group, params):
    return {"y": jnp.asarray(label > 0, jnp.float32),
            "sigma": float(params.get("sigmoid", 1.0))}


def gradients(state, score, dtype=jnp.float32):
    sigma = state["sigma"]
    s = score.astype(dtype)
    p = jax.nn.sigmoid(sigma * s)
    g = (p - state["y"].astype(dtype)) * sigma
    h = p * (1 - p) * sigma * sigma
    return g.astype(jnp.float32), h.astype(jnp.float32)
