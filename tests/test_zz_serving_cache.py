"""Serving warmup x persistent compile cache (ISSUE 9 satellite).

Lives in its own ``zz``-named file ON PURPOSE: the test uses
``jax.clear_caches()`` as the process-restart stand-in, which drops the
in-memory jit cache for the WHOLE process — any test file collected
after it would silently re-lower (and re-backend-compile through the
persistent cache) every program it touches, inflating suite wall time
toward the tier-1 timeout. Alphabetical collection puts this file last,
so the damage lands after everything else has run.
"""
import numpy as np

import lightgbm_tpu as lgb

from utils import FAST_PARAMS, binary_data


def test_second_boot_rearms_ladder_with_zero_cache_misses(
        tmp_path, cache_config_restored):
    """With tpu_compile_cache_dir set, a restarted server re-warms its
    FULL predict ladder from the persistent cache — backend compiles
    consult the cache and miss zero times."""
    import jax
    X, _ = binary_data()
    params = dict(FAST_PARAMS, objective="binary",
                  tpu_predict_buckets="32,256",
                  tpu_compile_cache_dir=str(tmp_path / "cc"))
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train(params, lgb.Dataset(X, label=y), 3)
    # boot 1 must BACKEND-compile the whole ladder (earlier tests may
    # have left shape-compatible programs in the in-memory jit cache,
    # which would skip the backend and write nothing to disk)
    jax.clear_caches()
    boot1 = bst.warm_predict_ladder()
    assert boot1["cache"]["requests"] > 0          # cache consulted
    # "process restart": drop every in-memory jit/backend cache, so
    # the second warmup must re-lower and re-ask the backend
    jax.clear_caches()
    boot2 = bst.warm_predict_ladder()
    assert boot2["lowerings"] > 0                  # really re-lowered
    assert boot2["cache"]["requests"] > 0
    assert boot2["cache"]["misses"] == 0, boot2    # zero backend work
    assert boot2["cache"]["hits"] == boot2["cache"]["requests"]
    # warmed-from-cache programs really serve
    out, n = bst.predict_serving(X[:5])
    np.testing.assert_array_equal(out[:n], bst.predict(X[:5]))
