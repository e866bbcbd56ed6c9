"""The ranking gradients laid out by query length (objectives.py): held
against the benchmark's plain reference (benchmarks/reference/
lambdarank.py, which pads every query to the longest), bit for bit
against the passes that carried them before the slot-order core (scatter
to row order, ``argsort`` and ``take_along_axis``, gathers back), kept
here as the reference, and against the single-[Q, M] layout the program
had, kept here too; what the gradient program moves by index, from its
jaxpr; the first trees of ``lgb.train`` on uneven queries against the
reference's per-leaf sums, as benchmarks/correct.py holds them;
``rank_xendcg`` on uneven queries; the spans and counters of the
layout."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import correct  # noqa: E402
from benchmarks.reference import lambdarank as reference  # noqa: E402
from benchmarks.traffic.train_window import score_in_dataset_order  # noqa: E402
from lightgbm_tpu import objectives  # noqa: E402
from lightgbm_tpu.analysis.jaxpr import indexed_moves  # noqa: E402
from lightgbm_tpu.boosting.gbdt import _pad_metadata  # noqa: E402
from lightgbm_tpu.io.dataset import Metadata  # noqa: E402
from lightgbm_tpu.obs import flight  # noqa: E402

TRUNC = 30
#: 1, 2, the truncation level and one either side, both sides of the
#: first two class boundaries, one longer than every boundary but the
#: last (260 of 300: classes end at 128, 256 and 300), and the longest
UNEVEN = [1, 2, TRUNC - 1, TRUNC, TRUNC + 1, 7, 128, 129, 64, 256, 257, 260,
          300, 45, 200]
EQUAL_LABELS, EQUAL_SCORES = 13, 14       # the queries of 45 and of 200


def uneven(seed=0, lengths=UNEVEN):
    """Seeded labels 0-4 and scores on queries of those lengths; one query
    whose labels are all equal and one whose scores are."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int64)
    n = int(lengths.sum())
    label = rng.integers(0, 5, n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    if len(lengths) > EQUAL_SCORES:
        label[starts[EQUAL_LABELS]:starts[EQUAL_LABELS + 1]] = 2.0
        score[starts[EQUAL_SCORES]:starts[EQUAL_SCORES + 1]] = 0.25
    weight = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return label, score, lengths, weight


def objective(name, label, lengths, weight=None, rows_of_no_query=0,
              **config):
    """``rows_of_no_query``: so many rows after the last query, as the
    sharded learner pads them (zero weight, and so weights for all)."""
    md = Metadata(len(label))
    md.set_label(label)
    md.set_group(lengths)
    if weight is not None:
        md.set_weight(weight)
    if rows_of_no_query:
        md = _pad_metadata(md, len(label) + rows_of_no_query)
    obj = objectives.create_objective(name, dict(
        {"sigmoid": 1.0, "lambdarank_truncation_level": TRUNC}, **config))
    obj.init(md, md.num_data)
    return obj


def former_chunk_grads(obj, s, g, mask, inv_max_dcg):
    """``_query_chunk_grads`` as it was before the sorts carried their
    payload: ``argsort`` twice and five gathers along the query."""
    qc, m = s.shape
    t = min(obj.truncation_level, m)
    sig = obj.sigmoid
    order = jnp.argsort(-s, axis=1)
    rank_of = jnp.argsort(order, axis=1)
    s_s = jnp.take_along_axis(s, order, axis=1)
    g_s = jnp.take_along_axis(g, order, axis=1)
    m_s = jnp.take_along_axis(mask, order, axis=1)
    disc = 1.0 / jnp.log2(jnp.arange(m, dtype=jnp.float32) + 2.0)
    s_i, s_j = s_s[:, :t, None], s_s[:, None, :]
    g_i, g_j = g_s[:, :t, None], g_s[:, None, :]
    d_i, d_j = disc[None, :t, None], disc[None, None, :]
    upper = jnp.arange(t)[:, None] < jnp.arange(m)[None, :]
    pair_valid = (m_s[:, :t, None] & m_s[:, None, :]
                  & (g_i != g_j) & upper[None])
    delta_ndcg = jnp.abs((g_i - g_j) * (d_i - d_j)) \
        * inv_max_dcg[:, None, None]
    i_high = g_i > g_j
    ds_high = jnp.where(i_high, s_i - s_j, s_j - s_i)
    if obj.norm:
        n_valid = jnp.sum(m_s.astype(jnp.int32), axis=1)
        best = s_s[:, 0]
        worst = jnp.take_along_axis(
            s_s, jnp.maximum(n_valid - 1, 0)[:, None], axis=1)[:, 0]
        delta_ndcg = jnp.where(
            (best != worst)[:, None, None],
            delta_ndcg / (0.01 + jnp.abs(ds_high)), delta_ndcg)
    p = jax.nn.sigmoid(sig * ds_high)
    lam_h = sig * (p - 1.0) * delta_ndcg
    hes = sig * sig * p * (1.0 - p) * delta_ndcg
    lam_h = jnp.where(pair_valid, lam_h, 0.0)
    hes = jnp.where(pair_valid, hes, 0.0)
    lam_i = jnp.where(i_high, lam_h, -lam_h)
    pad_t = ((0, 0), (0, m - t))
    grad_sorted = jnp.pad(lam_i.sum(axis=2), pad_t) - lam_i.sum(axis=1)
    hess_sorted = jnp.pad(hes.sum(axis=2), pad_t) + hes.sum(axis=1)
    if obj.norm:
        sum_lambdas = 2.0 * (-lam_h).sum(axis=(1, 2))
        scale = jnp.where(
            sum_lambdas > 0,
            jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-15),
            1.0)
        grad_sorted = grad_sorted * scale[:, None]
        hess_sorted = hess_sorted * scale[:, None]
    return (jnp.take_along_axis(grad_sorted, rank_of, axis=1),
            jnp.take_along_axis(hess_sorted, rank_of, axis=1))


def former_gradients(obj, score):
    """``get_gradients`` as it was: each class gathers its scores from row
    order, every row gathers its gradient and its hessian from the
    classes' slots, and the row weights multiply in row order."""
    by_class, row_slot = objectives._queries_by_length(
        obj.metadata.query_boundaries, obj.num_data)
    per_class = []
    for (_, idx), cls in zip(by_class, obj.query_classes):
        idx = jnp.asarray(idx)
        mask = idx >= 0
        q, m = idx.shape
        s = jnp.where(mask, score[jnp.maximum(idx, 0)], -jnp.inf)
        chunk, n_chunks = obj._chunks(q)
        rows = ((0, chunk * n_chunks - q), (0, 0))
        grad_q, hess_q = jax.lax.map(
            lambda a: former_chunk_grads(obj, *a),
            (jnp.pad(s, rows, constant_values=-jnp.inf).reshape(
                n_chunks, chunk, m),
             jnp.pad(cls.gain, rows).reshape(n_chunks, chunk, m),
             jnp.pad(mask, rows).reshape(n_chunks, chunk, m),
             jnp.pad(cls.inv_max_dcg, rows[0]).reshape(n_chunks, chunk)))
        per_class.append((grad_q.reshape(-1, m)[:q].reshape(-1),
                          hess_q.reshape(-1, m)[:q].reshape(-1)))
    zero = jnp.zeros((1,), score.dtype)
    row_slot = jnp.asarray(row_slot)
    grad = jnp.concatenate([g for g, _ in per_class] + [zero])[row_slot]
    hess = jnp.concatenate([h for _, h in per_class] + [zero])[row_slot]
    return obj._weighted(grad, hess)


def former_in_order(obj, score, rows):
    """The compact grower's gradient program as it was: scores scattered
    to row order, ``get_gradients``, both gathered back."""
    in_rows = jnp.zeros_like(score).at[rows].set(score)
    grad, hess = former_gradients(obj, in_rows)
    return grad[rows], hess[rows]


def padded_gradients(obj, score):
    """The layout the program had: every query padded to the longest,
    one [Q, M] index and mask, a scatter of every slot."""
    idx, _ = objectives._pad_queries(obj.metadata.query_boundaries)
    idx = jnp.asarray(idx)
    mask = idx >= 0
    q, m = idx.shape
    safe_idx = jnp.maximum(idx, 0)
    s = jnp.where(mask, score[safe_idx], -jnp.inf)
    g = jnp.where(mask, obj.row_gain[safe_idx], 0.0)
    inv_max_dcg = np.zeros(q, np.float32)
    for c in obj.query_classes:
        inv_max_dcg[c.queries] = np.asarray(c.inv_max_dcg)
    chunk = min(obj._QUERY_CHUNK, q)
    q_pad = (-q) % chunk
    s = jnp.pad(s, ((0, q_pad), (0, 0)), constant_values=-jnp.inf)
    g = jnp.pad(g, ((0, q_pad), (0, 0)))
    length = jnp.pad(mask.sum(axis=1, dtype=jnp.int32), (0, q_pad))
    imd = jnp.pad(jnp.asarray(inv_max_dcg), (0, q_pad))
    n_chunks = (q + q_pad) // chunk
    grad_q, hess_q = jax.lax.map(
        lambda a: obj._query_chunk_grads(*a),
        (s.reshape(n_chunks, chunk, m), g.reshape(n_chunks, chunk, m),
         length.reshape(n_chunks, chunk), imd.reshape(n_chunks, chunk)))
    grad_q = grad_q.reshape(-1, m)[:q]
    hess_q = hess_q.reshape(-1, m)[:q]
    grad = jnp.zeros_like(score).at[safe_idx.reshape(-1)].add(
        jnp.where(mask, grad_q, 0.0).reshape(-1))
    hess = jnp.zeros_like(score).at[safe_idx.reshape(-1)].add(
        jnp.where(mask, hess_q, 0.0).reshape(-1))
    return obj._weighted(grad, hess)


def close(got, want, rtol=2e-5):
    """To float32 rounding of sums over a query's pairs: against the
    largest entry, since single entries cancel to all but zero."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) <= rtol * np.max(np.abs(want))


# ------- (a) the plain reference, and bit for bit the passes that went
def gradients_by(order, obj, former, score, seed):
    """Gradients in row order by the program (or, ``former``, by the
    passes it had): ``rows`` straight through ``get_gradients``, as the
    masked grower calls it; ``permuted`` through the compact grower's
    program, the rows in a seeded order of their own."""
    if order == "rows":
        return jax.jit(functools.partial(former_gradients, obj) if former
                       else obj.get_gradients)(score)
    rows = jnp.asarray(np.random.default_rng(seed).permutation(
        len(score)).astype(np.int32))
    g, h = jax.jit(functools.partial(former_in_order, obj) if former
                   else obj.gradients_in_order)(score[rows], rows)
    back = jnp.argsort(rows)
    return g[back], h[back]


@pytest.mark.parametrize("order", ["rows", "permuted", "no_query_rows"])
@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weights"])
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "no_norm"])
@pytest.mark.parametrize("trunc", [TRUNC, 5], ids=["t30", "t5"])
def test_gradients_match_the_plain_reference(trunc, norm, weights, order):
    label, score, lengths, weight = uneven(seed=trunc)
    extra = 3 if order == "no_query_rows" else 0
    obj = objective("lambdarank", label, lengths,
                    weight if weights else None, rows_of_no_query=extra,
                    lambdarank_norm=norm, lambdarank_truncation_level=trunc)
    assert obj.rank_counters["rank_classes"] == 3
    n = len(label)
    score_all = jnp.asarray(np.concatenate(
        [score, np.full(extra, 0.5, np.float32)]))
    by = "rows" if order == "rows" else "permuted"
    g, h = gradients_by(by, obj, False, score_all, seed=trunc)
    # the same bits as the passes that carried them before
    want_g, want_h = gradients_by(by, obj, True, score_all, seed=trunc)
    assert np.array_equal(np.asarray(g), np.asarray(want_g))
    assert np.array_equal(np.asarray(h), np.asarray(want_h))
    assert not np.asarray(g)[n:].any() and not np.asarray(h)[n:].any()
    g, h = g[:n], h[:n]
    state = reference.prepare(label, lengths, {
        "sigmoid": 1.0, "lambdarank_truncation_level": trunc,
        "lambdarank_norm": norm})
    want_g, want_h = reference.gradients(state, jnp.asarray(score))
    if weights:
        want_g, want_h = want_g * weight, want_h * weight
    close(g, want_g)
    close(h, want_h)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    for q in (0, EQUAL_LABELS):      # one document; no pair of two labels
        rows = slice(starts[q], starts[q + 1])
        assert not np.asarray(g)[rows].any() and not np.asarray(h)[rows].any()
    rows = slice(starts[EQUAL_SCORES], starts[EQUAL_SCORES + 1])
    assert np.asarray(h)[rows].any()


# ------------------------------------------- (b) the layout it replaces
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "no_norm"])
@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weights"])
def test_layout_by_length_matches_the_padded_layout(weights, norm):
    label, score, lengths, weight = uneven(seed=3)
    obj = objective("lambdarank", label, lengths,
                    weight if weights else None, lambdarank_norm=norm)
    g, h = jax.jit(obj.get_gradients)(jnp.asarray(score))
    want_g, want_h = jax.jit(lambda s: padded_gradients(obj, s))(
        jnp.asarray(score))
    close(g, want_g, rtol=1e-6)
    close(h, want_h, rtol=1e-6)


@pytest.mark.parametrize("length", [1, 40, 120, 300])
def test_one_length_is_one_class_and_bit_for_bit(length):
    label, score, lengths, _ = uneven(seed=length, lengths=[length] * 37)
    obj = objective("lambdarank", label, lengths)
    assert obj.rank_counters == {
        "rank_slots": 37 * length, "rank_docs": 37 * length,
        "rank_slots_per_doc": 1.0, "rank_classes": 1}
    g, h = jax.jit(obj.get_gradients)(jnp.asarray(score))
    want_g, want_h = jax.jit(lambda s: padded_gradients(obj, s))(
        jnp.asarray(score))
    assert np.array_equal(np.asarray(g), np.asarray(want_g))
    assert np.array_equal(np.asarray(h), np.asarray(want_h))


@pytest.mark.parametrize("lengths, classes, slots", [
    ([5, 128, 129, 256], [1, 1, 2, 2], 2 * 128 + 2 * 256),
    ([1024, 1025, 2048, 2049, 5000], [8, 9, 9, 10, 11],
     1024 + 2 * 2048 + 2049 + 5000),
    ([0, 3, 0], [1, 1, 1], 3 * 3),
])
def test_length_classes(lengths, classes, slots):
    assert objectives._length_class(np.array(lengths)).tolist() == classes
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    by_class, row_slot = objectives._queries_by_length(bounds, bounds[-1] + 2)
    assert sum(idx.size for _, idx in by_class) == slots
    flat = np.concatenate([idx.reshape(-1) for _, idx in by_class] + [[-1]])
    # every row reads the slot that holds it; the rows of no query read
    # the one past the last
    assert np.array_equal(flat[row_slot[:bounds[-1]]], np.arange(bounds[-1]))
    assert row_slot[bounds[-1]:].tolist() == [slots, slots]


# ---------------- what the gradient program moves by index, by its jaxpr
@pytest.mark.parametrize("rows_of_no_query", [0, 3])
def test_the_program_moves_rows_to_slots_and_back_by_one_index(
        rows_of_no_query):
    """One scatter carries the scores from the grower's order to the
    slots and one two-wide gather both gradients back, by one composed
    index (a gather itself); in a class two sorts, and no gather along
    the query. The passes it had are counted beside it."""
    label, score, lengths, _ = uneven(seed=1)
    obj = objective("lambdarank", label, lengths,
                    rows_of_no_query=rows_of_no_query)
    n = len(label) + rows_of_no_query
    slots = 9 * 128 + 3 * 256 + 3 * 300
    args = (jnp.zeros((n,), jnp.float32), jnp.arange(n, dtype=jnp.int32))
    moves = indexed_moves(jax.make_jaxpr(obj.gradients_in_order)(*args))
    by_op = {op: [m for m in moves if m["op"] == op]
             for op in ("gather", "scatter", "sort")}
    assert sorted(m["shape"] for m in by_op["gather"]) == [(n,), (n, 2)]
    assert [m["shape"] for m in by_op["scatter"]] == [(n,)]
    assert [m["accesses"] for m in moves if m["op"] != "sort"] == [n] * 3
    per_class = [(128, 9), (256, 3), (300, 3)]
    assert sorted(m["shape"] for m in by_op["sort"]) == sorted(
        [(q, m) for m, q in per_class] * 2)
    was = indexed_moves(jax.make_jaxpr(
        lambda s, r: former_in_order(obj, s, r))(*args))
    assert sum(m["op"] == "sort" for m in was) == len(by_op["sort"])
    assert sum(m["op"] == "scatter" for m in was) == 1
    # the scatter, each class's scores, five gathers along the query and
    # the norm's read of the worst score, two gathers back to rows and
    # two to the grower's order (and a class's four cuts of the top
    # positions, which jnp spelt as gathers of one index)
    assert sum(m["accesses"] for m in was) == (
        n + 6 * slots + sum(q + 4 for _, q in per_class) + 4 * n)


# ------------------------ (c) the first trees against the per-leaf sums
@pytest.mark.parametrize("grower", ["masked", "compact"])
def test_first_trees_hold_the_reference_sums(grower):
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(11)
    lengths = np.asarray(UNEVEN * 4)
    n = int(lengths.sum())
    X = np.abs(rng.normal(size=(n, 6))).astype(np.float32)
    rel = X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.normal(size=n)
    label = np.searchsorted(np.quantile(rel, [0.5, 0.75, 0.9, 0.97]),
                            rel).astype(np.float32)
    params = {"objective": "lambdarank", "num_leaves": 7, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0,
              "sigmoid": 1.0, "lambdarank_truncation_level": TRUNC,
              "lambdarank_norm": True, "tpu_grower": grower, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=label, group=lengths,
                                        params=params),
                    num_boost_round=correct.CHECKED_TREES,
                    keep_training_booster=True)
    assert bst._gbdt._use_compact == (grower == "compact")
    produced = {"model_text": bst.model_to_string(),
                "train_score": score_in_dataset_order(bst),
                "first_window_tree": 0,
                "window_iterations": correct.CHECKED_TREES, "seed": 0}
    readings = correct.reference_readings(
        produced, {"XT": np.ascontiguousarray(X.T), "label": label,
                   "group": lengths}, {"params": params})
    ok, rows = correct.judge(readings, {
        "trees_missing": 0, "leaf_count_wrong": 0, "score_gap": 1e-5,
        "leaf_value_gap": 1e-4, "leaf_hessian_gap": 1e-4,
        "split_gain_gap": 1e-4})
    assert ok, rows


# ----------------------------------------------------- (d) rank_xendcg
@pytest.mark.parametrize("weights", [False, True], ids=["plain", "weights"])
def test_rank_xendcg_on_uneven_queries(weights):
    label, score, lengths, weight = uneven(seed=5)
    obj = objective("rank_xendcg", label, lengths,
                    weight if weights else None)
    g, h = obj.get_gradients(jnp.asarray(score))
    # the same draw, query by query in plain numpy
    m = int(lengths.max())
    _, sub = jax.random.split(jax.random.PRNGKey(obj.seed))
    gam = np.asarray(jax.random.gamma(sub, 1.0, shape=(len(lengths), m)))
    starts = np.concatenate([[0], np.cumsum(lengths)])
    want_g, want_h = np.zeros(len(label)), np.zeros(len(label))
    for q, n in enumerate(lengths):
        rows = slice(starts[q], starts[q + 1])
        rho = (2.0 ** label[rows].astype(np.float64) - 1.0) / gam[q, :n]
        target = rho / max(rho.sum(), 1e-15)
        p = np.exp(score[rows].astype(np.float64) - score[rows].max())
        p /= p.sum()
        want_g[rows], want_h[rows] = p - target, np.maximum(p * (1 - p),
                                                            1e-15)
    if weights:
        want_g, want_h = want_g * weight, want_h * weight
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=0, atol=2e-6)


# ------------------------------------------------- spans and counters
@pytest.mark.parametrize("what", ["rank_layout", "rank_grads", "counters",
                                  "moves"])
def test_layout_spans_and_counters(what):
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(2)
    lengths = np.asarray(UNEVEN)
    n = int(lengths.sum())
    X = rng.normal(size=(n, 4)).astype(np.float32)
    label = rng.integers(0, 5, n).astype(np.float32)
    flight.recorder().clear()
    bst = lgb.Booster({"objective": "lambdarank", "num_leaves": 7,
                       "min_data_in_leaf": 5, "tpu_grower": "compact",
                       "verbosity": -1},
                      lgb.Dataset(X, label=label, group=lengths))
    bst.update()
    events = flight.recorder().events()
    spans = [e for e in events if e["event"] == "span"]
    if what == "rank_layout":
        (layout,) = [e for e in spans if e["name"] == "rank_layout"]
        assert layout["parent"] == "booster_init"
        assert layout["t1"] >= layout["t0"]
    elif what == "rank_grads":
        (grads,) = [e for e in spans if e["name"] == "rank_grads"]
        assert grads["parent"] == "iteration" and grads["iteration"] == 0
        assert not [e for e in spans if e["name"] == "gradient"]
    elif what == "counters":
        (tick,) = [e for e in events if e["event"] == "iteration"]
        slots = 9 * 128 + 3 * 256 + 3 * 300
        assert tick["rank_slots"] == slots and tick["rank_docs"] == n
        assert tick["rank_slots_per_doc"] == pytest.approx(slots / n)
        assert tick["rank_classes"] == 3 and tick["dispatches"] == 2
    else:
        # what the booster's own gradient program holds, by its jaxpr
        (tick,) = [e for e in events if e["event"] == "iteration"]
        gbdt = bst._gbdt
        moves = indexed_moves(jax.make_jaxpr(gbdt._rank_grads_fn())(
            gbdt._compact["work"], gbdt.train_score,
            gbdt._compact["rank_grad_layout"]))
        for op, count in (("gather", 2), ("scatter", 1), ("sort", 6)):
            assert tick[f"rank_{op}s"] == count == sum(
                m["op"] == op for m in moves)
        assert tick["rank_moved_per_doc"] == 3.0 == sum(
            m["accesses"] for m in moves) / n


def test_the_gradient_program_takes_the_layout_as_an_argument():
    """Held arrays traced into the program would be its constants: the
    index of every slot in its text, and another program for every order
    the same queries come in."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(4)
    lengths = np.asarray(UNEVEN)
    n = int(lengths.sum())
    bst = lgb.Booster({"objective": "lambdarank", "num_leaves": 7,
                       "min_data_in_leaf": 5, "tpu_grower": "compact",
                       "verbosity": -1},
                      lgb.Dataset(rng.normal(size=(n, 4)).astype(np.float32),
                                  label=rng.integers(0, 5, n).astype(
                                      np.float32), group=lengths))
    bst.update()
    gbdt = bst._gbdt
    program, state = gbdt._rank_grads_fn(), gbdt._compact
    layout = state["rank_grad_layout"]
    assert layout is not None
    slots = gbdt.objective.rank_counters["rank_slots"]
    as_argument = program.lower(state["work"], gbdt.train_score,
                                layout).as_text()
    as_constants = program.lower(state["work"], gbdt.train_score).as_text()
    # an int32 index alone prints as eight hex digits a slot
    assert len(as_argument) + 8 * slots < len(as_constants)
    g1, h1 = program(state["work"], gbdt.train_score, layout)
    g2, h2 = program(state["work"], gbdt.train_score)
    assert np.array_equal(np.asarray(g1), np.asarray(g2))
    assert np.array_equal(np.asarray(h1), np.asarray(h2))
