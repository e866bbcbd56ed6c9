"""Device TreeSHAP: exact per-feature contributions as one XLA program.

The serving twin of ops/treeshap.py (the numpy reference of Lundberg et
al.'s exact TreeSHAP — the algorithm ``Tree::TreeSHAP`` implements in the
reference's src/io/tree.cpp, driven from ``GBDT::PredictContrib``). The
host walk is O(rows * trees * leaves * depth^2) Python recursion; here
the same arithmetic is reshaped for a batched accelerator:

  * the recursion is unrolled per LEAF: every root->leaf path is
    extracted once at stack time (``build_shap_paths``) into
    depth-bucketed arrays — the internal node ids along the path, the
    direction the path takes, and the per-path-step -> unique-feature
    slot mapping (the reference's duplicate-feature UNWIND merges
    repeated features on a path; the merge STRUCTURE and the merged
    cover fractions are row-independent, so they precompute);
  * per (row, leaf): the row's agreement with each path step comes from
    the SAME packed per-node records the depth-walk predict engine
    gathers (ops/predict._pack_node_records — go_left bit-parity with
    routing), merged per slot into the row-dependent ``one`` fractions;
    EXTEND then runs as a vectorized recurrence over the depth bucket
    and the per-slot UNWIND sums run as one masked scan — O(depth^2)
    like the reference, but over [tree-chunk, rows, depth] lanes with no
    data-dependent control flow;
  * trees run ``tbatch`` at a time under a chunk scan with per-chunk
    class scatter-add, exactly like ``predict_raw_batched``, so the
    compiled program is keyed on (row rung, tree bucket, depth bucket,
    num_class) — the coalescer's zero-recompile serving contract extends
    to the ``pred_contrib`` endpoint unchanged.

Numerics: pweights accumulate in float32 on device (the host reference
is float64); contributions match the numpy path within documented f32
tolerance and sum to the raw score (tests/test_device_serving.py pins
both properties, multiclass and windowed models included).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .packed import gather_bin
from .predict import (StackedTrees, _REC_BIN, _REC_CAT, _REC_COL, _REC_DL,
                      _REC_NAN, _pack_node_records)
from .treeshap import tree_expected_value


class ShapPaths(NamedTuple):
    """Per-leaf decision paths, depth-bucketed and tree-padded.

    ``D`` is the depth bucket, ``L`` the padded leaf width, ``T`` the
    tree bucket. Slot 0 of the unique-path axis is the root placeholder
    (zero fraction 1, one fraction 1 — its contribution weight is
    identically 0); padded steps point at slot 0 and padded slots keep
    (1, 1) fractions, so they are arithmetic no-ops.
    """

    node: jax.Array       # [T, L, D] i32 internal node per step, -1 pad
    went_left: jax.Array  # [T, L, D] bool — direction the PATH takes
    slot: jax.Array       # [T, L, D] i32 unique-feature slot (1-based)
    zfrac: jax.Array      # [T, L, D+1] f32 merged cover fractions, 1.0 pad
    feat: jax.Array       # [T, L, D+1] i32 feature id per slot (0 pad)
    ulen: jax.Array       # [T, L] i32 unique path length (0 = no path)
    ev: jax.Array         # [T] f32 cover-weighted expected value


def build_shap_paths(models: Sequence, max_leaves: int, depth_pad: int,
                     pad_to: Optional[int] = None) -> ShapPaths:
    """Extract every tree's per-leaf paths on the host (numpy, once per
    model window at stack time — the row-independent half of TreeSHAP).

    Cover fractions multiply in float64 and round once to f32, like the
    leaf values the predict stack carries. Padding trees (``pad_to`` >
    len(models)) and constant trees get ``ulen == 0`` everywhere: their
    leaves contribute nothing and only ``ev`` (0 for padding) reaches
    the bias slot."""
    t = len(models)
    t_pad = max(t, pad_to or t)
    L, D = max_leaves, depth_pad
    node = np.full((t_pad, L, D), -1, np.int32)
    went = np.zeros((t_pad, L, D), bool)
    slot = np.zeros((t_pad, L, D), np.int32)
    zfrac = np.ones((t_pad, L, D + 1), np.float64)
    feat = np.zeros((t_pad, L, D + 1), np.int32)
    ulen = np.zeros((t_pad, L), np.int32)
    ev = np.zeros(t_pad, np.float32)
    for ti, m in enumerate(models):
        ev[ti] = tree_expected_value(
            m.left_child, m.right_child, m.leaf_value, m.internal_count,
            m.leaf_count, m.num_nodes)
        if m.num_nodes == 0:
            continue

        def cover(nd: int) -> float:
            if nd < 0:
                return max(float(m.leaf_count[-(nd + 1)]), 1e-12)
            return max(float(m.internal_count[nd]), 1e-12)

        # iterative DFS carrying the (internal node, direction, child)
        # path; leaves fill their row with the first-occurrence slot
        # merge (extend order is immaterial in exact arithmetic — the
        # reference's unwind/re-extend moves merged features to the end,
        # a pure rounding-order difference)
        stack = [(0, [])]
        while stack:
            nd, path = stack.pop()
            if nd < 0:
                leaf = -(nd + 1)
                if len(path) > D:
                    raise ValueError(
                        f"path of {len(path)} steps exceeds the depth "
                        f"bucket {D}")
                slots = {}
                for s, (inode, wl, child) in enumerate(path):
                    node[ti, leaf, s] = inode
                    went[ti, leaf, s] = wl
                    f = int(m.split_feature[inode])
                    if f not in slots:
                        slots[f] = len(slots) + 1
                        feat[ti, leaf, slots[f]] = f
                    j = slots[f]
                    slot[ti, leaf, s] = j
                    zfrac[ti, leaf, j] *= cover(child) / cover(inode)
                ulen[ti, leaf] = len(slots)
                continue
            lc, rc = int(m.left_child[nd]), int(m.right_child[nd])
            stack.append((lc, path + [(nd, True, lc)]))
            stack.append((rc, path + [(nd, False, rc)]))
    return ShapPaths(
        jnp.asarray(node), jnp.asarray(went), jnp.asarray(slot),
        jnp.asarray(zfrac.astype(np.float32)), jnp.asarray(feat),
        jnp.asarray(ulen), jnp.asarray(ev))


def _chunked(arr: jax.Array, chunks: int) -> jax.Array:
    return arr.reshape(chunks, arr.shape[0] // chunks, *arr.shape[1:])


def _path_agreement(binned, rec_b, cat_b, node, went, slot, depth: int,
                    any_cat: bool, packed: bool) -> jax.Array:
    """Per-slot ``one`` fractions [Tb, N, D+1] in {0, 1}: a slot is 1
    when the row agrees with EVERY occurrence of its feature on the
    path (go_left bit-parity with the predict walk: same records, same
    predicate). Padded steps land on slot 0 with forced agreement."""
    nd = jnp.maximum(node, 0)                                  # [Tb, D]
    r = jnp.take_along_axis(rec_b, nd[:, :, None], axis=1)     # [Tb, D, 7]
    n = binned.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    col = r[..., _REC_COL][:, :, None]
    fcol = gather_bin(binned, rows, col, packed)               # [Tb, D, N]
    go_left = (fcol <= r[..., _REC_BIN][:, :, None]) | \
        ((r[..., _REC_DL][:, :, None] != 0)
         & (fcol == r[..., _REC_NAN][:, :, None]))
    if any_cat:
        w = cat_b.shape[-1]
        idx = jnp.broadcast_to(nd[:, :, None], nd.shape + (w,))
        words = jnp.take_along_axis(cat_b, idx, axis=1)        # [Tb, D, W]
        word_id = (fcol // 32).astype(jnp.uint32)
        sel = jnp.zeros_like(fcol, dtype=jnp.uint32)
        for j in range(w):
            sel = jnp.where(word_id == j, words[..., j][:, :, None], sel)
        in_set = ((sel >> (fcol.astype(jnp.uint32) % 32)) & 1) != 0
        go_left = jnp.where(r[..., _REC_CAT][:, :, None] != 0, in_set,
                            go_left)
    agree = (go_left == went[:, :, None]) | (node[:, :, None] < 0)

    # a slot's one is the AND of its occurrences' agreements; padded steps
    # land on slot 0 with forced agreement, so slot 0 stays (1, 1)
    onehot_slot = (slot[:, :, None]
                   == jnp.arange(depth + 1, dtype=jnp.int32)[None, None, :])
    disagree = (~agree).astype(jnp.float32)                    # [Tb, D, N]
    cnt = jnp.einsum("tdn,tdj->tnj", disagree,
                     onehot_slot.astype(jnp.float32))
    return (cnt == 0).astype(jnp.float32)                      # [Tb, N, D+1]


def _scatter_to_features(wgt, onehot_f) -> jax.Array:
    """Per-slot SHAP weights [Tb, N, D+1] summed onto their features
    [Tb, N, Fd] through a 0/1 matrix. HIGHEST precision is load-bearing:
    at the TPU's default an f32 contraction rounds its operands to bf16,
    which is exact for the one-hot but not for the weights — the first run
    on a v5e had contributions 4.2e-4 off the host twin (1.2e-7 on the CPU
    backend, where the default is already full f32)."""
    return jnp.einsum("tnj,tjf->tnf", wgt, onehot_f,
                      precision=jax.lax.Precision.HIGHEST)


def _extend_unwind(one, zfrac, ulen, depth: int) -> jax.Array:
    """The row-dependent EXTEND/UNWIND recurrences: per-slot UNWIND sums
    [Tb, B, D+1] from the agreement fractions ``one`` [Tb, B, D+1].

    ``B`` is any batch axis — rows in the serving kernel, enumerated
    agreement masks in the deploy-time table builder
    (:func:`build_shap_tables`): the arithmetic depends on the row ONLY
    through ``one``, which is what makes the tables row-independent."""
    tb, b = one.shape[0], one.shape[1]
    zero = zfrac[:, None, :]                                   # [Tb, 1, D+1]

    # -- EXTEND: vectorized pweight recurrence over slots 1..u -------------
    karr = jnp.arange(depth + 1, dtype=jnp.float32)
    p0 = jnp.zeros((tb, b, depth + 1), jnp.float32).at[..., 0].set(1.0)

    def ext_body(j, p):
        jf = j.astype(jnp.float32)
        z = jnp.take(zfrac, j, axis=1)[:, None, None]          # [Tb, 1, 1]
        o = jnp.take(one, j, axis=2)[..., None]                # [Tb, B, 1]
        pshift = jnp.pad(p, ((0, 0), (0, 0), (1, 0)))[..., :-1]
        newp = (z * p * (jf - karr) + o * pshift * karr) / (jf + 1.0)
        return jnp.where((j <= ulen)[:, None, None], newp, p)

    p = lax.fori_loop(1, depth + 1, ext_body, p0)

    # -- UNWIND sums for every slot (masked descent i = u-1 .. 0) ----------
    uf = ulen.astype(jnp.float32)[:, None, None]               # [Tb, 1, 1]
    pu = jnp.take_along_axis(p, ulen[:, None, None], axis=2)   # [Tb, B, 1]
    next_one = jnp.broadcast_to(pu, p.shape)
    total = jnp.zeros_like(p)

    def unwind_body(s, carry):
        total, next_one = carry
        i = ulen - 1 - s                                       # [Tb]
        valid = (i >= 0)[:, None, None]
        iq = jnp.maximum(i, 0)
        i_f = iq.astype(jnp.float32)[:, None, None]
        pi = jnp.take_along_axis(p, iq[:, None, None], axis=2)  # [Tb, B, 1]
        safe_one = jnp.where(one != 0, one, 1.0)
        tmp = next_one * (uf + 1.0) / ((i_f + 1.0) * safe_one)
        frac = zero * (uf - i_f) / (uf + 1.0)
        zero_term = pi / jnp.where(frac != 0, frac, 1.0)
        add = jnp.where(one != 0, tmp, zero_term)
        nn = jnp.where(one != 0, pi - tmp * frac, next_one)
        return (jnp.where(valid, total + add, total),
                jnp.where(valid, nn, next_one))

    total, _ = lax.fori_loop(0, depth, unwind_body, (total, next_one))
    return total


def _leaf_phi(binned, rec_b, cat_b, leaf, depth: int, any_cat: bool,
              packed: bool):
    """SHAP contributions of ONE leaf across a tree chunk: [Tb, N, D+1]
    per-slot weights ``w * (one - zero) * leaf_value`` plus the slot
    feature ids to scatter them with."""
    node, went, slot, zfrac, feat, ulen, lval = leaf
    one = _path_agreement(binned, rec_b, cat_b, node, went, slot, depth,
                          any_cat, packed)
    total = _extend_unwind(one, zfrac, ulen, depth)
    zero = zfrac[:, None, :]                                   # [Tb, 1, D+1]
    # padded slots carry (one, zero) == (1, 1) so their weight is exactly
    # 0; slot 0 likewise — no masking needed beyond the fractions
    return total * (one - zero) * lval[:, None, None], feat


@functools.partial(jax.jit, static_argnames=(
    "num_class", "depth", "tbatch", "any_cat", "packed", "num_features"))
def shap_batched(
    binned: jax.Array,         # [N, F] u8/u16, or [N, ceil(F/2)] u8 packed
    trees: StackedTrees,       # T padded to the tree bucket
    paths: ShapPaths,
    nan_bin_arr: jax.Array,    # [F] i32
    is_cat_arr: jax.Array,     # [F] bool
    num_model_per_iteration: jax.Array,  # scalar i32
    num_class: int = 1,
    depth: int = 8,            # depth bucket (paths are built at it)
    tbatch: int = 16,
    any_cat: bool = False,
    packed: bool = False,
    num_features: int = 0,
    col_of: Optional[jax.Array] = None,
) -> jax.Array:
    """SHAP contributions [num_class, N, F+1] (bias in the last column).

    Row rung, tree bucket, depth bucket and num_class are the only jit
    keys — identical to the predict engine's serving contract, so a
    warmed ``pred_contrib`` ladder serves mixed batch sizes with zero
    steady-state compiles.
    """
    from ..obs.spans import span
    with span("contrib"):
        n = binned.shape[0]
        t_total = trees.num_trees
        chunks = t_total // tbatch
        k_it = jnp.maximum(num_model_per_iteration, 1)
        rec = _pack_node_records(trees, nan_bin_arr, is_cat_arr, col_of)
        class_ids = (jnp.arange(t_total, dtype=jnp.int32) % k_it)
        xs = (_chunked(rec, chunks), _chunked(trees.cat_bitset, chunks),
              _chunked(trees.leaf_value, chunks),
              _chunked(paths.node, chunks), _chunked(paths.went_left, chunks),
              _chunked(paths.slot, chunks), _chunked(paths.zfrac, chunks),
              _chunked(paths.feat, chunks), _chunked(paths.ulen, chunks),
              _chunked(paths.ev, chunks), _chunked(class_ids, chunks))
        fdim = num_features + 1
        farange = jnp.arange(fdim, dtype=jnp.int32)

        def chunk_step(scores, x):
            (rec_b, cat_b, lv_b, node_b, went_b, slot_b, zfrac_b, feat_b,
             ulen_b, ev_b, cid_b) = x
            tb = rec_b.shape[0]

            def leaf_step(phi, leaf_x):
                wgt, feat = _leaf_phi(binned, rec_b, cat_b, leaf_x, depth,
                                      any_cat, packed)
                onehot_f = (feat[:, :, None] == farange[None, None, :]
                            ).astype(jnp.float32)              # [Tb,D+1,Fd]
                return phi + _scatter_to_features(wgt, onehot_f), None

            # scan the leaf axis (leaf-major transposes of the path
            # arrays) so peak memory stays one leaf's working set
            leaf_xs = (
                node_b.transpose(1, 0, 2), went_b.transpose(1, 0, 2),
                slot_b.transpose(1, 0, 2), zfrac_b.transpose(1, 0, 2),
                feat_b.transpose(1, 0, 2), ulen_b.T, lv_b.T)
            phi0 = jnp.zeros((tb, n, fdim), jnp.float32)
            phi, _ = lax.scan(leaf_step, phi0, leaf_xs)
            # the tree's expected value lands in the bias slot once
            phi = phi.at[..., -1].add(ev_b[:, None])
            if num_class == 1:
                return scores + phi.sum(axis=0)[None], None
            return scores.at[cid_b].add(phi), None

        scores0 = jnp.zeros((num_class, n, fdim), jnp.float32)
        scores, _ = lax.scan(chunk_step, scores0, xs)
        return scores


class ShapTables(NamedTuple):
    """Precomputed per-leaf UNWIND tables (the deploy-time half of the
    tabled contrib kernel).

    The EXTEND/UNWIND arithmetic of :func:`_extend_unwind` depends on
    the row ONLY through the binary agreement pattern ``one`` over the
    leaf's <= ``mask_bits`` unique slots (slot 0 and padded slots are
    forced to 1). Enumerating all ``2^mask_bits`` patterns at deploy
    time collapses the per-row kernel to agreement bits + one table
    gather + the feature scatter: ``table[t, l, m]`` already carries
    ``unwind_total * (one - zero) * leaf_value`` per slot.
    """

    node: jax.Array       # [T, L, D] i32 internal node per step, -1 pad
    went_left: jax.Array  # [T, L, D] bool — direction the PATH takes
    slot: jax.Array       # [T, L, D] i32 unique-feature slot (1-based)
    feat: jax.Array       # [T, L, D+1] i32 feature id per slot (0 pad)
    table: jax.Array      # [T, L, 2^mask_bits, D+1] f32 final weights
    ev: jax.Array         # [T] f32 cover-weighted expected value

    @property
    def mask_bits(self) -> int:
        return max(int(self.table.shape[2]).bit_length() - 1, 0)


def shap_table_bytes(tree_bucket: int, max_leaves: int, mask_bits: int,
                     depth: int) -> int:
    """f32 footprint of a :class:`ShapTables.table` slab — the budget
    gate (``tpu_shap_table_mb``) checks this BEFORE building."""
    return tree_bucket * max_leaves * (1 << mask_bits) * (depth + 1) * 4


@functools.partial(jax.jit, static_argnames=("mask_bits", "depth"))
def build_shap_tables(paths: ShapPaths, leaf_value: jax.Array,
                      mask_bits: int, depth: int) -> ShapTables:
    """Enumerate every agreement mask through EXTEND/UNWIND once, at
    deploy time (row-independent — runs on model (hot-)swap, never on
    the serving path).

    ``mask_bits`` must cover the longest unique path
    (``paths.ulen.max()``); build peak memory is ~4x the final table, so
    the caller gates on :func:`shap_table_bytes` first. Bit ``j-1`` of a
    mask is slot ``j``'s agreement; slots past a leaf's ``ulen`` are
    forced to agree, matching what :func:`_path_agreement` yields for
    real rows (no step maps to a slot past ``ulen``), so every reachable
    mask row is exact — table-vs-loop parity is bit-level per leaf.
    """
    t, l, d1 = paths.zfrac.shape
    m = 1 << mask_bits
    zfrac = paths.zfrac.reshape(t * l, d1)
    ulen = paths.ulen.reshape(t * l)
    lval = leaf_value.astype(jnp.float32).reshape(t * l)
    j = jnp.arange(d1, dtype=jnp.int32)
    bits = (jnp.arange(m, dtype=jnp.int32)[:, None]
            >> jnp.maximum(j - 1, 0)[None, :]) & 1              # [M, D+1]
    forced = (j[None, None, :] == 0) | (j[None, None, :]
                                        > ulen[:, None, None])  # [TL,1,D+1]
    one = jnp.where(forced, 1.0, bits[None].astype(jnp.float32))
    total = _extend_unwind(one, zfrac, ulen, depth)             # [TL,M,D+1]
    wgt = total * (one - zfrac[:, None, :]) * lval[:, None, None]
    return ShapTables(
        node=paths.node, went_left=paths.went_left, slot=paths.slot,
        feat=paths.feat, table=wgt.reshape(t, l, m, d1), ev=paths.ev)


@functools.partial(jax.jit, static_argnames=(
    "num_class", "depth", "tbatch", "any_cat", "packed", "num_features"))
def shap_batched_tables(
    binned: jax.Array,         # [N, F] u8/u16, or [N, ceil(F/2)] u8 packed
    trees: StackedTrees,       # T padded to the tree bucket
    tables: ShapTables,
    nan_bin_arr: jax.Array,    # [F] i32
    is_cat_arr: jax.Array,     # [F] bool
    num_model_per_iteration: jax.Array,  # scalar i32
    num_class: int = 1,
    depth: int = 8,            # depth bucket (paths are built at it)
    tbatch: int = 16,
    any_cat: bool = False,
    packed: bool = False,
    num_features: int = 0,
    col_of: Optional[jax.Array] = None,
) -> jax.Array:
    """Tabled twin of :func:`shap_batched`: [num_class, N, F+1].

    Per (row, leaf) the EXTEND and UNWIND recurrences are replaced by a
    mask-integer reduction over the agreement bits and ONE gather from
    the precomputed table — same jit keys, same output (bit-identical to
    the loop kernel on every reachable mask, see
    :func:`build_shap_tables`)."""
    from ..obs.spans import span
    with span("contrib"):
        n = binned.shape[0]
        t_total = trees.num_trees
        chunks = t_total // tbatch
        k_it = jnp.maximum(num_model_per_iteration, 1)
        rec = _pack_node_records(trees, nan_bin_arr, is_cat_arr, col_of)
        class_ids = (jnp.arange(t_total, dtype=jnp.int32) % k_it)
        mask_bits = tables.mask_bits
        xs = (_chunked(rec, chunks), _chunked(trees.cat_bitset, chunks),
              _chunked(tables.node, chunks),
              _chunked(tables.went_left, chunks),
              _chunked(tables.slot, chunks), _chunked(tables.feat, chunks),
              _chunked(tables.table, chunks), _chunked(tables.ev, chunks),
              _chunked(class_ids, chunks))
        fdim = num_features + 1
        farange = jnp.arange(fdim, dtype=jnp.int32)
        pw2 = jnp.left_shift(
            jnp.int32(1), jnp.arange(mask_bits, dtype=jnp.int32))

        def chunk_step(scores, x):
            (rec_b, cat_b, node_b, went_b, slot_b, feat_b, tab_b, ev_b,
             cid_b) = x
            tb = rec_b.shape[0]

            def leaf_step(phi, leaf_x):
                node, went, slot, feat, tab = leaf_x    # tab [Tb, M, D+1]
                one = _path_agreement(binned, rec_b, cat_b, node, went,
                                      slot, depth, any_cat, packed)
                bits = (one[..., 1:mask_bits + 1] != 0).astype(jnp.int32)
                midx = jnp.sum(bits * pw2[None, None, :], axis=-1)  # [Tb,N]
                wgt = jnp.take_along_axis(
                    tab, jnp.broadcast_to(midx[:, :, None],
                                          (tb, n, tab.shape[2])), axis=1)
                onehot_f = (feat[:, :, None] == farange[None, None, :]
                            ).astype(jnp.float32)              # [Tb,D+1,Fd]
                return phi + _scatter_to_features(wgt, onehot_f), None

            leaf_xs = (
                node_b.transpose(1, 0, 2), went_b.transpose(1, 0, 2),
                slot_b.transpose(1, 0, 2), feat_b.transpose(1, 0, 2),
                tab_b.transpose(1, 0, 2, 3))
            phi0 = jnp.zeros((tb, n, fdim), jnp.float32)
            phi, _ = lax.scan(leaf_step, phi0, leaf_xs)
            phi = phi.at[..., -1].add(ev_b[:, None])
            if num_class == 1:
                return scores + phi.sum(axis=0)[None], None
            return scores.at[cid_b].add(phi), None

        scores0 = jnp.zeros((num_class, n, fdim), jnp.float32)
        scores, _ = lax.scan(chunk_step, scores0, xs)
        return scores
