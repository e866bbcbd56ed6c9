"""Traffic kind ``train_window``: boosting iterations in a closed loop.

Set-up is the data from the seed, ``Dataset.construct()``, the first
``lgb.train`` round (``keep_training_booster=True``) and ``warmup_updates``
untimed ``Booster.update()`` calls. The window then calls ``update()`` on
that same booster, each call waiting for ``train_score``, until
``seconds`` have passed; the iteration in flight is finished and counted.
``attempted`` and ``failed`` count iterations. With ``--trace 1`` the
window also ends after ``trace_max_iterations``.

Parameters (the cell's ``traffic_params``): ``warmup_updates``,
``trace_max_iterations``, and ``max_iterations`` (unset in a cell: the
control's short window sets it).
"""
import gc
import time

import numpy as np


def resolved_engine(bst):
    """The engine fields of the booster's GrowerParams as resolved (after
    chip_smoke.resolved_engine)."""
    g = bst._gbdt
    gp = g.grower_params
    return {
        "grower": "compact" if g._use_compact else "masked",
        "entry": g._engine_resolution.entry_id,
        "fused_block": gp.fused_block, "fused_dual": gp.fused_dual,
        "hist_impl": gp.hist_impl, "hist_mbatch": gp.hist_mbatch,
        "hist_layout": gp.hist_layout, "hist_overlap": gp.hist_overlap,
        "step_buckets": gp.step_buckets, "quant_hist": gp.quant_hist,
        "bin_pack4": gp.bin_pack4,
    }


def score_in_dataset_order(bst):
    """The training score, [N] float32 on the host, in the dataset's row
    order: the compact grower keeps its rows, and the score with them, in
    the order its partitions left them."""
    g = bst._gbdt
    raw = np.asarray(g.train_score)[0]
    if getattr(g, "_compact", None) is None:
        return raw[:g._n_real]
    out = np.empty_like(raw)
    out[g._compact_perm()] = raw
    return out[:g._n_real]


def run(env, cell, config, seed, seconds, trace):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards

    tp = cell.get("traffic_params", {})
    params = dict(config["params"])
    with env.span("data"):
        data = env.generator(config["generator"]).make(seed,
                                                       **config["sizes"])
    with env.span("construct"):
        ds = lgb.Dataset(data["XT"].T, label=data["label"],
                         group=data["group"], params=params)
        ds.construct()
    with guards.cache_counter() as cache:
        with env.span("first_iter"):
            bst = lgb.train(params, ds, num_boost_round=1,
                            keep_training_booster=True)
            bst._gbdt.train_score.block_until_ready()
        with env.span("warmup_updates"):
            for _ in range(int(tp.get("warmup_updates", 1))):
                bst.update()
                bst._gbdt.train_score.block_until_ready()
    first_window_tree = bst.num_trees()
    env.note("engine", resolved_engine(bst))
    env.note("compile_cache", {"requests": cache.requests,
                               "hits": cache.hits})
    env.setup_done()

    max_iters = (int(tp.get("trace_max_iterations", 3)) if trace
                 else tp.get("max_iterations"))
    iter_s, failed = [], 0
    with guards.compile_counter() as compiles, env.window(trace):
        t0 = last = time.perf_counter()
        while True:
            with env.span("update"):
                try:
                    bst.update()
                    bst._gbdt.train_score.block_until_ready()
                except Exception as err:          # counted, and fatal
                    env.note("update_failed", repr(err))
                    failed += 1
                    break
            now = time.perf_counter()
            iter_s.append(now - last)
            last = now
            if now - t0 >= seconds or len(iter_s) == max_iters:
                break
    env.read_memory_peak()
    done = len(iter_s)
    env.note("iteration_s", [round(t, 4) for t in iter_s])
    produced = {"model_text": bst.model_to_string(),
                "train_score": score_in_dataset_order(bst),
                "first_window_tree": first_window_tree,
                "window_iterations": done, "seed": seed}
    del bst, ds
    gc.collect()
    return {
        "attempted": done + failed, "failed": failed, "iterations": done,
        "end_to_end": {"train_s_per_iter":
                       (last - t0) / done if done else None},
        "counters": {"lowerings_in_window": compiles.lowerings},
        "produced": produced, "data": data,
    }
