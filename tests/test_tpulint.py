"""tpulint: tier-1 wiring + per-rule fixture tests + allowlist workflow.

The whole-package test IS the tier-1 gate: any non-allowlisted finding in
lightgbm_tpu/ fails the suite. The fixture snippets encode each rule's
seed case (the pre-fix code from ADVICE r5) so a regression of the
analyzer — or of the fixed code — fails loudly.
"""
import os
import textwrap

import pytest

import lightgbm_tpu
from lightgbm_tpu.analysis.tpulint import (DEFAULT_ALLOWLIST, apply_allowlist,
                                           check_allowlist_staleness,
                                           lint_paths, load_allowlist, main)

PKG_DIR = os.path.dirname(lightgbm_tpu.__file__)


def lint_snippet(tmp_path, source, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    findings, errors = lint_paths([str(p)])
    assert not errors, errors
    return findings


def codes(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------- tier-1
def test_package_is_clean():
    """The shipped tree has zero non-allowlisted findings, and every
    allowlist entry carries a justification and is actually used."""
    findings, errors = lint_paths([PKG_DIR])
    assert not errors, errors
    entries, allow_errors = load_allowlist(DEFAULT_ALLOWLIST)
    assert not allow_errors, allow_errors
    remaining = apply_allowlist(findings, entries)
    assert not remaining, "\n".join(f.render() for f in remaining)
    unused = [e.render() for e in entries if not e.used]
    assert not unused, f"unused allowlist entries: {unused}"


def test_cli_exit_zero_on_package():
    assert main([PKG_DIR]) == 0


# ---------------------------------------------------------------- R001
def test_r001_host_sync_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            v = float(x)
            a = np.asarray(x)
            jax.device_get(x)
            i = x.sum().item()
            return v, a, i
    """)
    assert codes(findings).count("R001") >= 4


def test_r001_host_constants_not_flagged(tmp_path):
    """float() on trace-time host config (closures, module constants) is
    fine — only traced values sync."""
    findings = lint_snippet(tmp_path, """
        import jax

        ALPHA = "0.5"

        def build(cfg):
            @jax.jit
            def step(x):
                return x * float(ALPHA) + float(cfg.beta)
            return step
    """)
    assert not findings


def test_r001_host_code_not_flagged(tmp_path):
    """Un-jitted host code may sync freely (treeshap-style host loops)."""
    findings = lint_snippet(tmp_path, """
        import numpy as np

        def host_summary(arr):
            return float(np.asarray(arr).sum())
    """)
    assert not findings


def test_r001_snapshot_io_in_jit_flagged(tmp_path):
    """Seed: checkpoint/snapshot file I/O (open, pickle.dump, fsync)
    reachable from jit-traced code is a host-sync finding."""
    findings = lint_snippet(tmp_path, """
        import os
        import pickle

        import jax

        @jax.jit
        def step_with_snapshot(x):
            with open("/tmp/snap.ckpt", "wb") as fh:
                pickle.dump(x, fh)
                os.fsync(fh.fileno())
            return x * 2
    """)
    assert codes(findings).count("R001") >= 3


def test_r001_snapshot_io_reached_from_jit_flagged(tmp_path):
    """Same hazard one call away: a snapshot helper referenced from a
    jitted step is jit-reachable and its file I/O is flagged."""
    findings = lint_snippet(tmp_path, """
        import pickle

        import jax

        def save_state(path, state):
            with open(path, "wb") as fh:
                pickle.dump(state, fh)

        @jax.jit
        def step(x):
            save_state("/tmp/s.ckpt", x)
            return x
    """)
    assert "R001" in codes(findings)


def test_r001_snapshot_writer_pinned_even_off_jit(tmp_path):
    """A pickle-and-fsync writer is a snapshot-writer site even in host
    code: every such function must be a reviewed, deliberate tick (the
    shipped io/checkpoint.py::write_snapshot carries the allowlist
    anchor)."""
    findings = lint_snippet(tmp_path, """
        import os
        import pickle

        def write_state(path, state):
            blob = pickle.dumps(state)
            with open(path, "wb") as fh:
                fh.write(blob)
                fh.flush()
                os.fsync(fh.fileno())
    """)
    assert "R001" in codes(findings)
    assert "snapshot-writer site" in findings[0].message


def test_r001_snapshot_reader_not_flagged(tmp_path):
    """Reading a snapshot on the host is fine: no pickle.dump, no jit."""
    findings = lint_snippet(tmp_path, """
        import pickle

        def read_state(path):
            with open(path, "rb") as fh:
                return pickle.loads(fh.read())
    """)
    assert not findings


# ---------------------------------------------------------------- R002
def test_r002_jit_in_loop(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax

        def build_all(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out
    """)
    assert "R002" in codes(findings)


def test_r002_unhashable_static_default(tmp_path):
    findings = lint_snippet(tmp_path, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def run(x, opts=[]):
            return x
    """)
    assert "R002" in codes(findings)


def test_r002_tracer_branch(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def step(x, flag):
            if flag:
                return x + 1
            return x
    """)
    assert "R002" in codes(findings)


def test_r002_unbucketed_predict_entry(tmp_path):
    """Sub-check (d) seed: a serving entry point feeding the raw request
    into a jitted callable keys the compiled program on the request
    shape — every distinct batch size recompiles (the 26-97s serving
    stalls the bucketed engine removed)."""
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _scores(x):
            return x * 2

        def predict(data):
            arr = jnp.asarray(data)
            return _scores(arr)
    """)
    assert "R002" in codes(findings)


def test_r002_bucketed_predict_entry_clean(tmp_path):
    """Flowing the request through a bucket/pad-named call clears the
    taint: the padded shape is a ladder rung, not the raw request size."""
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _scores(x):
            return x * 2

        def predict(data, rung):
            arr = pad_to_bucket(jnp.asarray(data), rung)
            return _scores(arr)
    """)
    assert "R002" not in codes(findings)


def test_r002_unbucketed_nonpredict_entry_not_flagged(tmp_path):
    """Training-loop callers are not serving entries; raw-shape jit args
    there are the normal fixed-shape train step."""
    findings = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def _step(x):
            return x + 1

        def train_one_iter(batch):
            return _step(batch)
    """)
    assert "R002" not in codes(findings)


def test_r002_static_shape_branch_not_flagged(tmp_path):
    """x.shape is static at trace time — branching on it is fine even
    when x itself is traced."""
    findings = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            if x.shape[0] > 4:
                return x[:4]
            return x
    """)
    assert not findings


def test_r002_static_branch_not_flagged(tmp_path):
    """Branching on declared static args is deliberate jax style."""
    findings = lint_snippet(tmp_path, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def step(x, mode):
            if mode == "fast":
                return x
            return -x
    """)
    assert not findings


def test_r002_interprocedural_static_helper_not_flagged(tmp_path):
    """A helper only ever called with static values stays static — but the
    same helper fed a traced value is flagged."""
    clean = lint_snippet(tmp_path, """
        import jax

        def helper(n):
            if n > 4:
                return 1.0
            return 2.0

        @jax.jit
        def step(x):
            return x * helper(3)
    """, name="clean.py")
    assert not clean
    dirty = lint_snippet(tmp_path, """
        import jax

        def helper(n):
            if n > 4:
                return 1.0
            return 2.0

        @jax.jit
        def step(x):
            return x * helper(x.sum())
    """, name="dirty.py")
    assert "R002" in codes(dirty)


def test_r002_unbucketed_grower_key(tmp_path):
    """Sub-check (e) seed: the raw config (num_leaves, max_depth) entering
    the GrowerParams jit key compiles one step program per exact tree
    shape — the 35-97 s training warmups the bucketed step ladder
    removed."""
    findings = lint_snippet(tmp_path, """
        def setup(cfg):
            gp = GrowerParams(
                num_leaves=int(cfg.get("num_leaves", 31)),
                max_depth=int(cfg.get("max_depth", -1)))
            return gp
    """)
    assert "R002" in codes(findings)


def test_r002_rung_mapped_grower_key_clean(tmp_path):
    """Flowing the budgets through a rung/bucket-named mapping clears the
    taint: the jit key carries the ladder rung, not the raw budget."""
    findings = lint_snippet(tmp_path, """
        def leaf_rung(n):
            r = 2
            while r < n:
                r *= 2
            return r

        def setup(cfg):
            rung = leaf_rung(int(cfg.get("num_leaves", 31)))
            gp = GrowerParams(num_leaves=rung, max_depth=-1)
            return gp
    """)
    assert "R002" not in codes(findings)


def test_r002_grower_key_replace_update(tmp_path):
    """The _replace-style key update (basic.py reset_parameter) is a sink
    too: re-keying on a raw budget mid-run recompiles just like the
    initial construction."""
    findings = lint_snippet(tmp_path, """
        def reset(self, booster):
            booster.grower_params = booster.grower_params._replace(
                num_leaves=int(self.config.num_leaves))
            return booster
    """)
    assert "R002" in codes(findings)


def test_r002_jitted_step_fed_raw_budget(tmp_path):
    """A jitted grower step called with a leaf-count-derived argument keys
    the program on the exact budget; the rung belongs in the key and the
    budget in a traced scalar."""
    findings = lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def grow_step(binned, budget):
            return binned

        def train(binned, cfg):
            leaves = int(cfg.get("num_leaves", 31))
            return grow_step(binned, leaves)
    """)
    assert "R002" in codes(findings)


def test_r002_raw_return_in_rung_mapping(tmp_path):
    """Sub-check (e) also pins the escape hatch: a rung/bucket mapping
    returning the raw budget IS the exact-keyed path and must carry an
    allowlist anchor (the shipped tpu_step_buckets=off branch in
    gbdt.bucketed_tree_shape does)."""
    findings = lint_snippet(tmp_path, """
        def tree_shape_bucket(bucketed, num_leaves, max_depth):
            if bucketed:
                return 2 * num_leaves, 1
            return num_leaves, max_depth
    """)
    assert "R002" in codes(findings)


# ---------------------------------------------------------------- R003
def test_r003_dtype_drift(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def step(x):
            y = np.sum(x)
            z = x.astype("float64")
            w = jnp.zeros(3, dtype="float64")
            q = x * jnp.float64(2.0)
            return y, z, w, q
    """)
    assert codes(findings).count("R003") >= 4


def test_r003_host_numpy_not_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        import numpy as np

        def host_stats(values):
            arr = np.asarray(values, np.float64)
            return np.sum(arr)
    """)
    assert not findings


def test_r003_int_matmul_needs_preferred_element_type(tmp_path):
    """The int-packing contract: int8 histogram contraction without
    preferred_element_type=int32 wraps the sums at +-127."""
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def hist(binned, codes):
            onehot = (binned[:, :, None] == jnp.arange(8)).astype(jnp.int8)
            ch = codes.astype(jnp.int8)
            return jnp.einsum("rfb,rk->fbk", onehot, ch)
    """)
    assert "R003" in codes(findings)


def test_r003_int_matmul_with_preferred_ok(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp
        from jax import lax

        @jax.jit
        def hist(binned, codes):
            onehot = (binned[:, :, None] == jnp.arange(8)).astype(jnp.int8)
            return jnp.einsum("rfb,rk->fbk", onehot,
                              codes.astype(jnp.int8),
                              preferred_element_type=jnp.int32)

        @jax.jit
        def perm(lt, sel):
            return lax.dot_general(
                lt, sel.astype("int8"),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
    """)
    assert not findings


def test_r003_dequantize_without_scale_flagged(tmp_path):
    """The dequantize contract: a bare f32 cast of a quantized histogram
    yields raw code sums, silently off by the per-iteration scale."""
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gains(qhist):
            g = qhist[:, :, 0].astype(jnp.float32)
            return g.sum()
    """)
    assert "R003" in codes(findings)


def test_r003_dequantize_with_scale_ok(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gains(qhist, g_scale):
            g = qhist[:, :, 0].astype(jnp.float32) * g_scale
            h = g_scale * qhist[:, :, 1].astype(jnp.float32)
            return g.sum() + h.sum()
    """)
    assert not findings


# ---------------------------------------------------------------- R004
def test_r004_env_override_unvalidated(tmp_path):
    """The seed case: boosting/gbdt.py:945 pre-fix (ADVICE r5 #3)."""
    findings = lint_snippet(tmp_path, """
        import os

        def pick_block(default_bs):
            bs = default_bs
            if os.environ.get("LGBM_TPU_FUSED_BS", ""):
                bs = int(os.environ["LGBM_TPU_FUSED_BS"])
            return bs
    """)
    assert "R004" in codes(findings)


def test_r004_validated_env_override_ok(tmp_path):
    findings = lint_snippet(tmp_path, """
        import os

        def _validated_block(value, cap):
            v = max(32, (int(value) // 32) * 32)
            return min(v, cap)

        def pick_block(cap):
            bs = _validated_block(os.environ["LGBM_TPU_FUSED_BS"], cap)
            return bs
    """)
    assert not findings


def test_r004_block_size_literal_and_num_rows(tmp_path):
    findings = lint_snippet(tmp_path, """
        def caller(work, scratch, args):
            return fused_split(work, scratch, *args, block_size=100)
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 2           # non-32-multiple AND missing num_rows
    clean = lint_snippet(tmp_path, """
        def caller(work, scratch, args, n):
            return fused_split(work, scratch, *args, block_size=128,
                               num_rows=n)
    """, name="clean_r4.py")
    assert not clean


def test_r004_mbatch_exceeds_mxu_rows(tmp_path):
    """8*mbatch must fit the 128 MXU rows (batched-M contract)."""
    findings = lint_snippet(tmp_path, """
        def caller(work, scratch, args, n):
            return fused_split(work, scratch, *args, block_size=128,
                               num_rows=n, mbatch=32)
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "MXU rows" in r4[0].message


def test_r004_mbatch_ring_over_vmem_budget(tmp_path):
    """pending_depth x block_size residency (ring slots + flush
    transients) must stay under the scoped-VMEM ring budget."""
    findings = lint_snippet(tmp_path, """
        def caller(work, scratch, args, n):
            return fused_split(work, scratch, *args, block_size=1024,
                               num_rows=n, mbatch=16)
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "scoped VMEM" in r4[0].message
    clean = lint_snippet(tmp_path, """
        def caller(work, scratch, args, n):
            return fused_split(work, scratch, *args, block_size=256,
                               num_rows=n, mbatch=8)
    """, name="clean_ring.py")
    assert not clean


def test_r004_pending_ring_missing_drain(tmp_path):
    """The missing-drain seed: a kernel staging histogram blocks into a
    pending ring keyed off mbatch, with no pushes % mbatch drain — the
    last partial batch would be silently dropped."""
    findings = lint_snippet(tmp_path, """
        from jax import lax

        def kernel(pendbuf, pendch, smem, mbatch):
            def hist_accum(rows, ch):
                pushes = smem[0]
                cur = lax.rem(pushes, mbatch)
                pendbuf[cur] = rows
                pendch[cur] = ch
                smem[0] = pushes + 1
            return hist_accum
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "drain" in r4[0].message
    clean = lint_snippet(tmp_path, """
        from jax import lax

        def kernel(pendbuf, pendch, smem, mbatch, flush):
            def hist_accum(rows, ch):
                pushes = smem[0]
                cur = lax.rem(pushes, mbatch)
                pendbuf[cur] = rows
                pendch[cur] = ch
                smem[0] = pushes + 1

            def hist_drain():
                pushes = smem[0]
                pending = lax.rem(pushes, mbatch)
                flush(pending)
            return hist_accum, hist_drain
    """, name="clean_drain.py")
    assert not clean


def test_r004_sublane_layout_bins_bound(tmp_path):
    """Bins-on-sublanes needs num_bins <= 64 (round 6): a constant
    sublane call with wider bins is a static contract violation."""
    findings = lint_snippet(tmp_path, """
        def caller(binned, ch):
            return pallas_histogram(binned, ch, num_bins=256,
                                    hist_layout="sublane")
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "sublane" in r4[0].message
    clean = lint_snippet(tmp_path, """
        def caller(binned, ch):
            return pallas_histogram(binned, ch, num_bins=64,
                                    hist_layout="sublane")
    """, name="clean_sublane.py")
    assert not clean


@pytest.mark.parametrize("layout", ["lane", "sublane"])
def test_r004_ring_budget_charged_under_both_layouts(tmp_path, layout):
    """Both layouts stage the same operands since PR 33 (each block
    transposed, as i32 rows, and its [8, bs] channels): a constant
    (block, depth) pair whose ring fits the budget passes under either,
    and one whose ring does not is rejected under either."""
    call = """
        def caller(work, scratch, args, n):
            return fused_split(work, scratch, *args, block_size=%d,
                               num_rows=n, mbatch=8,
                               hist_layout="%s")
    """
    fits = lint_snippet(tmp_path, call % (256, layout), name="ring_ok.py")
    assert not [f for f in fits if "VMEM" in f.message]
    over = lint_snippet(tmp_path, call % (384, layout), name="ring_over.py")
    r4 = [f for f in over if f.rule == "R004" and "VMEM" in f.message]
    assert len(r4) == 1, [f.render() for f in over]


def test_r004_engine_kwargs_outside_registry(tmp_path):
    """Engine-registry ownership seed (round 12): GrowerParams/._replace
    setting an engine knob outside lightgbm_tpu/engines from anything
    but a registry resolution re-opens a second selection site."""
    findings = lint_snippet(tmp_path, """
        def setup(cfg):
            return GrowerParams(num_leaves=31, hist_impl="pallas",
                                hist_mbatch=16)
    """)
    r4 = [f for f in findings if f.rule == "R004"
          and "registry" in f.message]
    assert len(r4) == 2, [f.render() for f in findings]
    clean = lint_snippet(tmp_path, """
        def setup(cfg, resolved):
            return GrowerParams(num_leaves=31,
                                hist_impl=resolved.hist_impl,
                                hist_mbatch=resolved.hist_mbatch,
                                fused_block=resolved.fused_block)
    """, name="clean_engine_kwargs.py")
    assert not [f for f in clean if "registry" in f.message]
    repl = lint_snippet(tmp_path, """
        def reset(gp, k):
            return gp._replace(hist_layout="sublane", hist_block=k)
    """, name="replace_engine.py")
    assert len([f for f in repl if f.rule == "R004"
                and "registry" in f.message]) == 1


def test_r004_engine_chooser_outside_registry(tmp_path):
    """A function choosing between engine-impl constants is selection
    POLICY — outside engines/ it is unowned (the ops/histogram.py
    _resolve_impl trace-time escape hatch is the one allowlist anchor)."""
    findings = lint_snippet(tmp_path, """
        def pick_engine(num_bins):
            if num_bins >= 128:
                return "pallas"
            return "xla"
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "engine" in r4[0].message
    # the same policy INSIDE the registry package is its home
    pkg = tmp_path / "engines"
    pkg.mkdir()
    (pkg / "registry.py").write_text(textwrap.dedent("""
        def pick_engine(num_bins):
            if num_bins >= 128:
                return "pallas"
            return "xla"
    """))
    in_registry, errors = lint_paths([str(pkg / "registry.py")])
    assert not errors
    assert not [f for f in in_registry if f.rule == "R004"]


def test_r004_constant_impl_callsite(tmp_path):
    """A histogram call pinning impl=/layout= to a constant hardcodes
    the engine at the callsite, bypassing the measured decision."""
    findings = lint_snippet(tmp_path, """
        def build(binned, ch, b):
            return histogram_block(binned, ch, b, impl="pallas",
                                   layout="sublane")
    """)
    r4 = [f for f in findings if f.rule == "R004"
          and "engine selection" in f.message]
    assert len(r4) == 2, [f.render() for f in findings]
    clean = lint_snippet(tmp_path, """
        def build(binned, ch, b, params):
            return histogram_block(binned, ch, b, impl=params.hist_impl,
                                   layout=params.hist_layout)
    """, name="clean_impl_passthrough.py")
    assert not clean
    # "auto" is not a selection — it defers to the anchored dispatch
    auto = lint_snippet(tmp_path, """
        def build(binned, ch, b):
            return histogram_block(binned, ch, b, impl="auto")
    """, name="auto_impl.py")
    assert not auto


def test_r004_engine_ownership_package_anchor():
    """The shipped tree's ONE engine-selection site outside engines/ is
    ops/histogram.py::_resolve_impl, carried by its allowlist anchor —
    with the allowlist applied the package is clean (the tier-1 test),
    without it exactly that site surfaces."""
    path = os.path.join(PKG_DIR, "ops", "histogram.py")
    findings, errors = lint_paths([path])
    assert not errors
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and r4[0].func == "_resolve_impl", \
        [f.render() for f in r4]
    entries, _ = load_allowlist(DEFAULT_ALLOWLIST)
    assert not apply_allowlist(r4, entries)


def test_r004_pack4_nibble_mask_detector(tmp_path):
    """pack4 unpack sites must mask with & 0xF (round 6): the unmasked
    shift leaves the neighbour feature's nibble in the high bits."""
    findings = lint_snippet(tmp_path, """
        def unpack_bins(packed_byte, feature):
            lo = packed_byte & 0xF
            hi = packed_byte >> 4
            return lo, hi
    """)
    r4 = [f for f in findings if f.rule == "R004"]
    assert len(r4) == 1 and "0xF" in r4[0].message
    dyn = lint_snippet(tmp_path, """
        def bin_col(packed_bins, j):
            byte = packed_bins[:, j // 2]
            return byte >> ((j & 1) * 4)
    """, name="dyn_shift.py")
    assert [f for f in dyn if f.rule == "R004"]
    clean = lint_snippet(tmp_path, """
        import jax.numpy as jnp

        def unpack_bins(packed_byte, feature):
            lo = packed_byte & jnp.uint8(0x0F)
            hi = (packed_byte >> 4) & jnp.uint8(0x0F)
            dyn = (packed_byte >> ((feature & 1) * 4)) & 0xF
            return lo, hi, dyn
    """, name="clean_nibble.py")
    assert not clean
    # unrelated shifts (word indices, radix unpacks) stay out of scope
    unrelated = lint_snippet(tmp_path, """
        def radix_unpack(sums):
            word = sums >> 5
            hi = sums >> 12
            return word, hi
    """, name="unrelated_shift.py")
    assert not unrelated


def test_r004_serving_entry_contract_coverage(tmp_path):
    """Serving-engine contract coverage seed (round 20): a serving
    EngineEntry must name an HLO contract id or a contract_exempt
    justification that points at the pinning test."""
    findings = lint_snippet(tmp_path, """
        SERVING_ENTRIES = (
            EngineEntry(id="serve_fast", impl="level", layout="heap",
                        description="no contract, no exemption"),
        )
    """)
    r4 = [f for f in findings if f.rule == "R004"
          and "serving EngineEntry" in f.message]
    assert len(r4) == 1 and "serve_fast" in r4[0].message
    vague = lint_snippet(tmp_path, """
        SERVING_ENTRIES = (
            EngineEntry(id="serve_q", impl="level", layout="heap",
                        contract_exempt="trust me"),
        )
    """, name="vague_exempt.py")
    r4 = [f for f in vague if f.rule == "R004"
          and "serving EngineEntry" in f.message]
    assert len(r4) == 1 and "pinning test" in r4[0].message
    clean = lint_snippet(tmp_path, """
        SERVING_ENTRIES = (
            EngineEntry(id="serve_walk", impl="walk", layout="packed",
                        contracts=("serve_walk",)),
            EngineEntry(id="serve_qleaf", impl="level", layout="heap",
                        contract_exempt="output pinned by the recorded "
                        "bound + tests/test_level_engine.py"),
            EngineEntry(id="xla_lane", impl="xla", layout="lane"),
        )
    """, name="clean_serving.py")
    assert not [f for f in clean if "serving EngineEntry" in f.message]


def test_r004_quant_bound_discarded(tmp_path):
    """Quantized-leaf recorded-bound seed (round 20): an unpack that
    drops quantize_leaves' bound, or a hand-rolled /127 scale with no
    bound/err assignment, serves quantized scores with no accuracy
    contract."""
    findings = lint_snippet(tmp_path, """
        def stack_quant(leaf_value, class_ids):
            slab, scale = quantize_leaves(leaf_value, class_ids, "int8")
            return slab, scale
    """)
    r4 = [f for f in findings if f.rule == "R004" and "bound" in f.message]
    assert len(r4) == 1
    underscore = lint_snippet(tmp_path, """
        def stack_quant(leaf_value, class_ids):
            slab, scale, _ = quantize_leaves(leaf_value, class_ids,
                                             "int8")
            return slab, scale
    """, name="underscore_bound.py")
    assert [f for f in underscore
            if f.rule == "R004" and "bound" in f.message]
    handrolled = lint_snippet(tmp_path, """
        import jax.numpy as jnp

        def quantize(v):
            amax = jnp.max(jnp.abs(v), axis=1)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            slab = jnp.round(v / scale[:, None]).astype(jnp.int8)
            return slab, scale
    """, name="handrolled_scale.py")
    r4 = [f for f in handrolled
          if f.rule == "R004" and "bound" in f.message]
    assert len(r4) == 1 and "127" not in r4[0].message.split(":")[0]
    clean = lint_snippet(tmp_path, """
        import jax.numpy as jnp

        def quantize(v):
            amax = jnp.max(jnp.abs(v), axis=1)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(v / scale[:, None]), -127, 127)
            err_t = jnp.max(jnp.abs(q * scale[:, None] - v), axis=1)
            return q.astype(jnp.int8), scale, jnp.max(err_t)

        def stack_quant(leaf_value, class_ids):
            slab, scale, bound = quantize_leaves(leaf_value, class_ids,
                                                 "int8")
            return slab, scale, float(bound)
    """, name="clean_quant.py")
    assert not [f for f in clean
                if f.rule == "R004" and "bound" in f.message]


# ---------------------------------------------------------------- R005
def test_r005_operand_shape_counting(tmp_path):
    """The seed case: parallel/comm_accounting.py:65 pre-fix (ADVICE r5
    #1) — async starts counted by operand shape."""
    findings = lint_snippet(tmp_path, """
        def collective_bytes(entries):
            total = 0
            for kind, shapes in entries:
                if kind.endswith("-start") and shapes:
                    shapes = shapes[:1]
                total += sum(shapes)
            return total
    """)
    assert "R005" in codes(findings)


def test_r005_result_shape_counting_ok(tmp_path):
    findings = lint_snippet(tmp_path, """
        RESULT_KINDS = ("all-gather-start", "collective-permute-start")

        def collective_bytes(entries):
            total = 0
            for kind, shapes in entries:
                if kind.endswith("-start") and shapes:
                    if kind in RESULT_KINDS:
                        shapes = shapes[1:2] if len(shapes) > 1 \\
                            else shapes[:1]
                    else:
                        shapes = shapes[:1]
                total += sum(shapes)
            return total
    """)
    assert not findings


def test_r004_fixed_gbdt_clean():
    """The LGBM_TPU_FUSED_BS override now routes through
    _validated_fused_block_env (ADVICE r5 #3) — no R004 findings."""
    path = os.path.join(PKG_DIR, "boosting", "gbdt.py")
    findings, errors = lint_paths([path])
    assert not errors
    assert not [f for f in findings if f.rule == "R004"], \
        [f.render() for f in findings]


def test_r005_fixed_module_clean():
    path = os.path.join(PKG_DIR, "parallel", "comm_accounting.py")
    findings, errors = lint_paths([path])
    assert not errors
    assert not [f for f in findings if f.rule == "R005"], \
        [f.render() for f in findings]


# ------------------------------------------------------- R005 extensions
def test_r005_inventory_missing_async_twin(tmp_path):
    """PR 2's psum_scatter lowers to reduce-scatter; an inventory with
    -start twins for other kinds but not reduce-scatter drops its bytes
    the day the HLO goes async."""
    findings = lint_snippet(tmp_path, """
        KINDS = ("all-reduce-start", "all-gather-start", "reduce-scatter",
                 "all-reduce", "all-gather")
    """)
    r5 = [f for f in findings if f.rule == "R005"]
    assert len(r5) == 1 and "reduce-scatter-start" in r5[0].message


def test_r005_inventory_with_twins_clean(tmp_path):
    findings = lint_snippet(tmp_path, """
        KINDS = ("all-reduce-start", "all-gather-start",
                 "reduce-scatter-start", "all-reduce", "all-gather",
                 "reduce-scatter")
    """)
    assert not findings


def test_r005_done_counting_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        def count(entries):
            total = 0
            for kind, nbytes in entries:
                if kind.endswith("-start"):
                    total += nbytes
                if kind.endswith("-done"):
                    total += nbytes
            return total
    """)
    assert any(f.rule == "R005" and "-done" in f.message for f in findings)


def test_r005_fixed_parser_module_clean():
    """analysis/hlo.py (the extracted parser) carries every async twin and
    counts result shapes — no R005 findings."""
    path = os.path.join(PKG_DIR, "analysis", "hlo.py")
    findings, errors = lint_paths([path])
    assert not errors
    assert not [f for f in findings if f.rule == "R005"], \
        [f.render() for f in findings]


# ---------------------------------------------------------------- R006
def test_r006_unknown_axis_name(tmp_path):
    findings = lint_snippet(tmp_path, """
        from jax import lax
        from jax.sharding import Mesh

        def make(devs):
            return Mesh(devs, axis_names=("data",))

        def step(x):
            return lax.psum_scatter(x, "dta")
    """)
    r6 = [f for f in findings if f.rule == "R006"]
    assert len(r6) == 1 and "'dta'" in r6[0].message


def test_r006_dimension_kwarg_does_not_mask_axis_name(tmp_path):
    """all_gather's `axis=` kwarg is an integer DIMENSION — it must not
    swallow a typo'd positional axis name."""
    findings = lint_snippet(tmp_path, """
        from jax import lax
        from jax.sharding import Mesh

        def make(devs):
            return Mesh(devs, axis_names=("data",))

        def step(x):
            return lax.all_gather(x, "dta", axis=0, tiled=True)
    """)
    r6 = [f for f in findings if f.rule == "R006"]
    assert len(r6) == 1 and "'dta'" in r6[0].message


def test_r006_declared_axis_and_dynamic_clean(tmp_path):
    findings = lint_snippet(tmp_path, """
        from jax import lax
        from jax.sharding import Mesh

        DATA_AXIS = "data"

        def make(devs):
            return Mesh(devs, axis_names=(DATA_AXIS,))

        def step(x, gp):
            a = lax.psum(x, DATA_AXIS)
            b = lax.psum(x, gp.axis_name)      # dynamic: skipped
            return a + b + lax.axis_index(DATA_AXIS)
    """)
    assert not [f for f in findings if f.rule == "R006"]


def test_r006_sharded_readback_without_gather(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        import numpy as np

        def bad(x, mesh, row_sharding):
            v = jax.device_put(x, row_sharding(mesh))
            return np.asarray(v)

        def gathered(x, mesh, row_sharding):
            v = jax.device_put(x, row_sharding(mesh))
            v = jax.device_get(v)
            return np.asarray(v)

        def replicated_ok(x, mesh, replicated):
            v = jax.device_put(x, replicated(mesh))
            return np.asarray(v)

        def named_replicated_ok(x, mesh):
            from jax.sharding import NamedSharding, PartitionSpec as P
            v = jax.device_put(x, NamedSharding(mesh, P()))
            return np.asarray(v)

        def named_sharded_bad(x, mesh):
            from jax.sharding import NamedSharding, PartitionSpec as P
            v = jax.device_put(x, NamedSharding(mesh, P("data")))
            return np.asarray(v)
    """)
    r6 = [f for f in findings if f.rule == "R006"]
    assert sorted(f.func for f in r6) == ["bad", "named_sharded_bad"]


# ---------------------------------------------------------------- R007
def test_r007_unlocked_public_method(tmp_path):
    findings = lint_snippet(tmp_path, """
        class Booster:
            def __init__(self):
                self._api_lock = RWLock()
                self.cache = None

            def predict(self, x):
                return x
    """)
    r7 = [f for f in findings if f.rule == "R007"]
    assert len(r7) == 1 and "predict" in r7[0].message


def test_r007_mutation_under_read_lock(tmp_path):
    """The _device_trees_cache pattern: a cache fill in a read-locked
    method interleaves with concurrent readers."""
    findings = lint_snippet(tmp_path, """
        class Booster:
            def __init__(self):
                self._api_lock = RWLock()
                self.cache = None

            @read_locked
            def predict(self, x):
                self.cache = x
                return x

            @write_locked
            def update(self):
                self.cache = None
    """)
    r7 = [f for f in findings if f.rule == "R007"]
    assert len(r7) == 1 and "READ lock" in r7[0].message


def test_r007_lockless_shared_class_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        class Dataset:
            def __init__(self):
                self.data = None

            def construct(self):
                self.data = 1
    """)
    r7 = [f for f in findings if f.rule == "R007"]
    assert len(r7) == 1 and "_api_lock" in r7[0].message


def test_r007_properly_locked_clean(tmp_path):
    findings = lint_snippet(tmp_path, """
        class Dataset:
            def __init__(self):
                self._api_lock = RWLock()
                self._inner = None

            @write_locked
            def construct(self):
                self._inner = 1
                return self

            @read_locked
            def num_data(self):
                return 0

            def _internal(self):
                self._inner = None     # private: caller holds the lock
    """)
    assert not [f for f in findings if f.rule == "R007"]


def test_r007_shipped_api_is_locked():
    """basic.py itself: every public Booster/Dataset method decorated."""
    path = os.path.join(PKG_DIR, "basic.py")
    findings, errors = lint_paths([path])
    assert not errors
    assert not [f for f in findings if f.rule == "R007"], \
        [f.render() for f in findings]


# ---------------------------------------------------------------- R008
def test_r008_unbounded_queue_flagged(tmp_path):
    """Seed: a serving class enqueuing into a maxsize-less queue — the
    slow-tick overload turns into unbounded latency instead of shedding."""
    findings = lint_snippet(tmp_path, """
        import queue

        class RequestServer:
            def __init__(self):
                self.q = queue.Queue()

            def submit(self, req):
                self.q.put_nowait(req)
    """)
    r8 = [f for f in findings if f.rule == "R008"]
    assert len(r8) == 1 and "maxsize" in r8[0].message


def test_r008_simplequeue_and_unbounded_deque_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        import collections
        import queue

        class Coalescer:
            def __init__(self):
                self.q = collections.deque()
                self.sq = queue.SimpleQueue()
    """)
    r8 = [f for f in findings if f.rule == "R008"]
    assert len(r8) == 2
    assert any("maxlen" in f.message for f in r8)
    assert any("SimpleQueue" in f.message for f in r8)


def test_r008_blocking_without_timeout_flagged(tmp_path):
    """Seed: request-path waits with no deadline — a wedged tick then
    wedges every caller instead of raising ServingTimeout."""
    findings = lint_snippet(tmp_path, """
        def serve_one(q, out, fut, fut2, ev):
            item = q.get()
            also = q.get(True)          # queue block flag, not a timeout
            out.put(item)
            late = fut2.result(None)    # explicit-None timeout blocks too
            ev.wait(timeout=None)
            return fut.result(), item, also, late
    """)
    r8 = [f for f in findings if f.rule == "R008"]
    assert len(r8) == 6
    assert all("timeout" in f.message for f in r8)
    assert any(".put()" in f.message for f in r8)   # producer-side twin


def test_r008_bounded_and_deadlined_clean(tmp_path):
    """Bounded queues + deadline-carrying waits are the contract; also:
    dict-style .get(key) and positional-timeout waits are not findings."""
    findings = lint_snippet(tmp_path, """
        import collections
        import queue

        class PredictionServer:
            def __init__(self, cfg):
                self.q = queue.Queue(maxsize=64)
                self.dq = collections.deque(maxlen=cfg.get("cap", 8))

            def submit(self, req, done, table):
                self.q.put(req, timeout=0.5)
                self.q.put(req, False)
                done.wait(0.5)
                table.get(req)              # dict-style get: not a wait
                return req.result(timeout=1.0)
    """)
    assert not [f for f in findings if f.rule == "R008"]


def test_r008_non_serving_scope_not_flagged(tmp_path):
    """The rule is scoped: the same patterns outside serving-named
    modules/classes/functions (training workers, IO pools) are not
    serving entry points."""
    findings = lint_snippet(tmp_path, """
        import queue

        class TrainWorker:
            def __init__(self):
                self.q = queue.Queue()

            def run(self, fut):
                return fut.result()
    """)
    assert not [f for f in findings if f.rule == "R008"]


def test_r008_shipped_serving_layer_needs_only_the_drain_anchor():
    """The shipped serving package has exactly one R008 finding — the
    deliberate graceful-drain join — and it is allowlist-anchored."""
    path = os.path.join(PKG_DIR, "serving")
    findings, errors = lint_paths([path])
    assert not errors
    r8 = [f for f in findings if f.rule == "R008"]
    assert len(r8) == 1 and r8[0].func.endswith("close"), \
        [f.render() for f in r8]
    entries, _ = load_allowlist(DEFAULT_ALLOWLIST)
    assert not apply_allowlist(r8, entries)


# ------------------------------------------------- R008 (c): featurize
def test_r008_host_featurize_in_tick_flagged(tmp_path):
    """Seed: a coalescer tick binning on the host — every tick pays the
    O(rows*features) numpy sweep the device featurizer replaces."""
    findings = lint_snippet(tmp_path, """
        from binning import bin_columns

        class MicroBatchCoalescer:
            def _tick(self, batch, mappers):
                return bin_columns(mappers, batch)
    """)
    r8 = [f for f in findings if "featurization" in f.message]
    assert len(r8) == 1 and "bin_columns" in r8[0].message


def test_r008_host_featurize_reachable_from_serve_entry_flagged(tmp_path):
    """Seed: the searchsorted sweep hides one call deep behind a serve
    entry — the reachability walk still pins it (at the helper)."""
    findings = lint_snippet(tmp_path, """
        import numpy as np

        def _bin_request(mappers, arr):
            return np.searchsorted(mappers, arr)

        def predict_serving(self, data):
            return _bin_request(self.mappers, data)
    """)
    r8 = [f for f in findings if "featurization" in f.message]
    assert len(r8) == 1 and "searchsorted" in r8[0].message
    assert r8[0].func.endswith("_bin_request")


def test_r008_host_featurize_outside_serving_clean(tmp_path):
    """The same calls outside serving scope (dataset construction, model
    export) are not findings — construct-time binning is the design."""
    findings = lint_snippet(tmp_path, """
        import numpy as np

        def fit_mappers(values, bounds):
            return np.searchsorted(bounds, values)

        def export_model(mapper, thr):
            return mapper.value_to_bin(thr)
    """)
    assert not [f for f in findings if "featurization" in f.message]


def test_r008_host_featurize_behind_train_boundary_clean(tmp_path):
    """The walk stops at train/construct entries: scripts/serve trains
    before taking traffic, and that boot-time bin pass is legitimate."""
    findings = lint_snippet(tmp_path, """
        from binning import bin_columns

        def train(data, mappers):
            return bin_columns(mappers, data)

        def serve_main(data, mappers):
            model = train(data, mappers)
            return model
    """)
    assert not [f for f in findings if "featurization" in f.message]


def test_r008_shipped_host_featurize_hatch_is_anchored():
    """The one shipped host-featurize site on a serving path is the
    tpu_serve_featurize=host escape hatch (GBDT.bin_matrix), and it is
    allowlist-anchored."""
    findings, errors = lint_paths([PKG_DIR])
    assert not errors
    feat = [f for f in findings if f.rule == "R008"
            and "featurization" in f.message]
    assert len(feat) == 1 and feat[0].func.endswith("bin_matrix"), \
        [f.render() for f in feat]
    entries, _ = load_allowlist(DEFAULT_ALLOWLIST)
    assert not apply_allowlist(feat, entries)


# ------------------------------------------------------------ allowlist
def test_allowlist_suppresses_and_tracks_usage(tmp_path):
    snippet = tmp_path / "mod.py"
    snippet.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            return float(x)
    """))
    allow = tmp_path / "allow.txt"
    allow.write_text(
        "R001 mod.py::step  # deliberate: scalar debug readback\n"
        "R003 other.py::nope  # never matches\n")
    findings, _ = lint_paths([str(snippet)])
    assert findings
    entries, errors = load_allowlist(str(allow))
    assert not errors
    remaining = apply_allowlist(findings, entries)
    assert not remaining
    assert entries[0].used and not entries[1].used


def test_allowlist_requires_justification(tmp_path):
    allow = tmp_path / "allow.txt"
    allow.write_text("R001 mod.py::step\n")
    entries, errors = load_allowlist(str(allow))
    assert not entries
    assert errors and "justification" in errors[0]


def test_allowlist_cli_errors_exit_2(tmp_path):
    snippet = tmp_path / "ok.py"
    snippet.write_text("x = 1\n")
    allow = tmp_path / "allow.txt"
    allow.write_text("R001 mod.py::step\n")
    assert main([str(snippet), "--allowlist", str(allow)]) == 2


# ------------------------------------------------- allowlist staleness
def test_check_allow_flags_dead_anchor(tmp_path):
    """Entries whose file::func anchor no longer matches the source are
    staleness errors — the allowlist cannot rot as code moves."""
    mod = tmp_path / "mod.py"
    mod.write_text("def live():\n    return 1\n")
    entries, errors = load_allowlist_text(
        tmp_path,
        "R001 mod.py::live  # still anchored\n"
        "R001 mod.py::dead_func  # function was deleted\n"
        "R002 gone.py::anything  # file was deleted\n")
    assert not errors
    stale = check_allowlist_staleness(entries, [str(tmp_path)])
    assert len(stale) == 2
    assert any("dead_func" in s for s in stale)
    assert any("gone.py" in s for s in stale)
    # wildcard funcs only need the file to exist
    entries2, _ = load_allowlist_text(tmp_path, "R003 mod.py::*  # module\n")
    assert not check_allowlist_staleness(entries2, [str(tmp_path)])


def load_allowlist_text(tmp_path, text):
    allow = tmp_path / "allow_stale.txt"
    allow.write_text(text)
    return load_allowlist(str(allow))


def test_check_allow_subset_lint_does_not_false_flag(tmp_path):
    """Linting a subtree must not report entries anchored elsewhere in
    the allowlist's package as stale — anchors resolve against the
    allowlist's own root too."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    (tmp_path / "a" / "mod.py").write_text("def live():\n    return 1\n")
    (tmp_path / "b" / "other.py").write_text("x = 1\n")
    allow = tmp_path / "allow.txt"
    allow.write_text("R001 a/mod.py::live  # anchored outside the subset\n")
    entries, _ = load_allowlist(str(allow))
    assert not check_allowlist_staleness(
        entries, [str(tmp_path / "b")], str(allow))
    # a genuinely dead anchor is still stale in the subset run
    allow.write_text("R001 a/mod.py::dead  # function deleted\n")
    entries, _ = load_allowlist(str(allow))
    assert check_allowlist_staleness(
        entries, [str(tmp_path / "b")], str(allow))


def test_check_allow_cli_exit_2(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text("x = 1\n")
    allow = tmp_path / "allow.txt"
    allow.write_text("R001 mod.py::deleted_fn  # anchor died\n")
    assert main([str(tmp_path), "--allowlist", str(allow),
                 "--check-allow"]) == 2
    # without the flag the entry is only an unused-entry warning
    assert main([str(tmp_path), "--allowlist", str(allow)]) == 0
    # an audit run (--no-allowlist) must still validate the anchors
    assert main([str(tmp_path), "--allowlist", str(allow),
                 "--no-allowlist", "--check-allow"]) == 2


def test_package_allowlist_staleness_clean():
    """Tier-1 wiring: the shipped allowlist has no stale anchors."""
    entries, errors = load_allowlist(DEFAULT_ALLOWLIST)
    assert not errors
    assert not check_allowlist_staleness(entries, [PKG_DIR])


# ---------------------------------------------------------------- R009
def test_r009_timing_in_jit_reachable_flagged(tmp_path):
    """Host-clock reads under jit (alias-aware) are findings: the values
    are trace-time constants at best, dispatch-time lies at worst."""
    findings = lint_snippet(tmp_path, """
        import time
        import time as _time
        from time import perf_counter
        import jax

        @jax.jit
        def step(x):
            t0 = time.time()
            t1 = _time.monotonic()
            t2 = perf_counter()
            return x * (t1 - t0) * t2
    """)
    assert codes(findings).count("R009") >= 3


def test_r009_manual_span_close_in_jit_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax
        from lightgbm_tpu.obs.spans import span

        @jax.jit
        def step(x):
            s = span("hist_build")
            y = x + 1
            s.close()
            return y
    """)
    assert any(f.rule == "R009" and "span" in f.message for f in findings)


def test_r009_clock_plus_dispatch_pinned(tmp_path):
    """Tick-site pinning: timing around a dispatching call without
    block_until_ready is a finding even OUTSIDE jit-reachable code."""
    findings = lint_snippet(tmp_path, """
        import time

        def bench_loop(booster):
            t0 = time.perf_counter()
            booster.train_step()
            return time.perf_counter() - t0
    """)
    r9 = [f for f in findings if f.rule == "R009"]
    assert r9 and "block_until_ready" in r9[0].message


def test_r009_block_until_ready_exempts(tmp_path):
    """The honest-timing escape: materializing before reading the clock
    again makes the measurement real — no finding."""
    findings = lint_snippet(tmp_path, """
        import time
        import jax

        def bench_loop(booster):
            t0 = time.perf_counter()
            out = booster.train_step()
            jax.block_until_ready(out)
            return time.perf_counter() - t0
    """)
    assert "R009" not in codes(findings)


def test_r009_plain_host_timing_clean(tmp_path):
    """A clock with no device dispatch in sight (queue bookkeeping, JSONL
    timestamps) is none of R009's business."""
    findings = lint_snippet(tmp_path, """
        import time

        def record(ring, fields):
            rec = {"t": time.time()}
            rec.update(fields)
            ring.append(rec)
    """)
    assert "R009" not in codes(findings)


def test_r009_with_span_under_jit_clean(tmp_path):
    """The with-scoped span form is the SUPPORTED spelling in traced
    code (named_scope at trace time) — not a finding."""
    findings = lint_snippet(tmp_path, """
        import jax
        from lightgbm_tpu.obs.spans import span

        @jax.jit
        def step(x):
            with span("hist_build"):
                return x + 1
    """)
    assert "R009" not in codes(findings)


# ---------------------------------------------------------------- R010
def test_r010_rank_guarded_collective_flagged(tmp_path):
    """The canonical pod deadlock: rank 0 joins a rendezvous its peers
    never enter."""
    findings = lint_snippet(tmp_path, """
        import jax
        from jax.experimental import multihost_utils as mu

        def sync_stats(x):
            if jax.process_index() == 0:
                return mu.process_allgather(x)
            return x
    """)
    assert "R010" in codes(findings)
    (f,) = [f for f in findings if f.rule == "R010"]
    assert "unmatched collective sequences" in f.message


def test_r010_env_rank_loop_bound_flagged(tmp_path):
    """Rank-var-derived loop trip counts disagree across the pod."""
    findings = lint_snippet(tmp_path, """
        import os
        import jax

        def drain(xs):
            rank = int(os.environ.get("LIGHTGBM_TPU_PROCESS_ID", "0"))
            for _ in range(rank):
                xs = jax.lax.psum(xs, "data")
            return xs
    """)
    assert "R010" in codes(findings)
    (f,) = [f for f in findings if f.rule == "R010"]
    assert "iteration count" in f.message


def test_r010_rank_guarded_early_exit_flagged(tmp_path):
    """A rank-conditional early return skips the barrier every other
    rank blocks in later."""
    findings = lint_snippet(tmp_path, """
        import os
        from lightgbm_tpu.parallel.mesh import sync_barrier

        def checkpoint(state):
            rank = int(os.environ["LIGHTGBM_TPU_PROCESS_ID"])
            if rank != 0:
                return None
            path = write_snapshot(state)
            sync_barrier("ckpt")
            return path
    """)
    assert "R010" in codes(findings)
    (f,) = [f for f in findings if f.rule == "R010"]
    assert "early exit" in f.message


def test_r010_while_on_rank_flagged(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax

        def settle(x):
            budget = jax.process_index() + 1
            while budget > 0:
                x = jax.lax.psum(x, "data")
                budget -= 1
            return x
    """)
    assert "R010" in codes(findings)


def test_r010_matched_arms_clean(tmp_path):
    """Every rank syncs, THEN branches on the gathered result — the
    reference's fixed-schedule discipline; both arms run the same
    collective sequence."""
    findings = lint_snippet(tmp_path, """
        import jax
        from jax.experimental import multihost_utils as mu

        def agree(x):
            r = jax.process_index()
            if r == 0:
                flag = mu.process_allgather(x)
            else:
                flag = mu.process_allgather(x * 0)
            return flag
    """)
    assert "R010" not in codes(findings)


def test_r010_process_count_guard_clean(tmp_path):
    """The ubiquitous distributed-at-all guard is uniform: when ranks
    could disagree on it there is no second rank to deadlock with
    (pool_bin_sample's own shape)."""
    findings = lint_snippet(tmp_path, """
        import jax
        from jax.experimental import multihost_utils as mu

        def pool(sample):
            if jax.process_count() <= 1:
                return sample
            return mu.process_allgather(sample)
    """)
    assert "R010" not in codes(findings)


def test_r010_nontrivial_process_count_flow_flagged(tmp_path):
    """process_count is only exempt in the literal distributed-at-all
    guard — arithmetic flows into a collective-bearing loop still
    fire (a half-configured launch makes it rank-varying)."""
    findings = lint_snippet(tmp_path, """
        import jax

        def ring(x):
            hops = jax.process_count() - 1
            for _ in range(hops):
                x = jax.lax.ppermute(x, "data", [(0, 1)])
            return x
    """)
    assert "R010" in codes(findings)


def test_r010_shipped_parallel_layer_needs_only_the_bootstrap_anchor():
    """The shipped multi-host plane lints R010-clean except the
    documented pre-bootstrap validation exit in init_distributed."""
    findings, errors = lint_paths(
        [os.path.join(PKG_DIR, "parallel"), os.path.join(PKG_DIR, "io")])
    assert not errors
    r010 = [f for f in findings if f.rule == "R010"]
    assert [f.func for f in r010] == ["init_distributed"]


# ---------------------------------------------------------------- R011
def r011(findings):
    return [f for f in findings if f.rule == "R011"]


def test_r011_lock_order_cycle_flagged(tmp_path):
    """Seed: two functions acquiring the same pair of module locks in
    opposite orders — the classic AB/BA deadlock, reported once with
    both witness chains."""
    findings = lint_snippet(tmp_path, """
        import threading

        MU_A = threading.Lock()
        MU_B = threading.Lock()

        def left():
            with MU_A:
                with MU_B:
                    pass

        def right():
            with MU_B:
                with MU_A:
                    pass
    """)
    cyc = [f for f in r011(findings) if "lock-order cycle" in f.message]
    assert len(cyc) == 1, [f.render() for f in findings]
    assert "left" in cyc[0].message and "right" in cyc[0].message


def test_r011_blocking_join_under_lock_flagged(tmp_path):
    """Seed: an untimed thread join while holding a lock — any other
    path into that lock now waits on the joined thread too."""
    findings = lint_snippet(tmp_path, """
        import threading

        class Worker:
            def __init__(self):
                self._mu = threading.Lock()
                self._thread = threading.Thread(target=print)

            def stop(self):
                with self._mu:
                    self._thread.join()
    """)
    hits = [f for f in r011(findings)
            if "blocking call under lock" in f.message
            and "join" in f.message]
    assert hits and hits[0].func == "stop"


def test_r011_blocking_reached_through_helper_flagged(tmp_path):
    """Interprocedural: the sleep sits two calls away from the lock —
    the finding lands at the holder and carries the call chain."""
    findings = lint_snippet(tmp_path, """
        import threading
        import time

        MU = threading.Lock()

        def backoff():
            time.sleep(1.0)

        def retry_step():
            backoff()

        def retry_under_lock():
            with MU:
                retry_step()
    """)
    hits = [f for f in r011(findings) if "time.sleep" in f.message]
    assert hits and hits[0].func == "retry_under_lock"
    assert "backoff" in hits[0].message and "retry_step" in hits[0].message


def test_r011_dispatch_under_write_lock_flagged(tmp_path):
    """Seed: jitted dispatch under an explicitly-taken write lock (the
    'hold the registry write lock across a device compile' class)."""
    findings = lint_snippet(tmp_path, """
        import jax
        from lightgbm_tpu.utils.rwlock import RWLock

        @jax.jit
        def kernel(x):
            return x * 2

        class Holder:
            def __init__(self):
                self._lock = RWLock()

            def swap(self, x):
                with self._lock.write():
                    return kernel(x)
    """)
    hits = [f for f in r011(findings)
            if "jitted dispatch under lock" in f.message]
    assert hits and hits[0].func == "swap"


def test_r011_read_write_upgrade_flagged(tmp_path):
    """Seed: a read-locked public method calling a write-locked one —
    RWLock raises at runtime; R011 finds the path statically."""
    findings = lint_snippet(tmp_path, """
        from lightgbm_tpu.utils.rwlock import RWLock, read_locked, \\
            write_locked

        class Store:
            def __init__(self):
                self._api_lock = RWLock()
                self.v = None

            @write_locked
            def commit(self, v):
                self.v = v

            @read_locked
            def peek(self):
                self.commit(None)
                return self.v
    """)
    hits = [f for f in r011(findings)
            if "read->write upgrade" in f.message]
    assert hits and hits[0].func == "peek"
    assert "commit" in hits[0].message


def test_r011_cv_wait_outside_loop_flagged(tmp_path):
    """Seed: Condition.wait under `if` instead of a predicate `while`
    loop — spurious wakeups and missed signals slip through."""
    findings = lint_snippet(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._cv = threading.Condition()
                self.ready = False

            def take(self):
                with self._cv:
                    if not self.ready:
                        self._cv.wait(1.0)
                    return self.ready
    """)
    hits = [f for f in r011(findings)
            if "outside a predicate loop" in f.message]
    assert hits and hits[0].func == "take"


def test_r011_clean_patterns_not_flagged(tmp_path):
    """Negative: while-looped timed cv wait, notify under the cv,
    consistent AB ordering, and re-entrant same-lock nesting are all
    the blessed patterns — zero findings."""
    findings = lint_snippet(tmp_path, """
        import threading

        class Pipeline:
            def __init__(self):
                self._cv = threading.Condition()
                self._mu = threading.Lock()
                self.items = []

            def produce(self, x):
                with self._cv:
                    self.items.append(x)
                    self._cv.notify()

            def consume(self):
                with self._cv:
                    while not self.items:
                        self._cv.wait(0.1)
                    return self.items.pop(0)

        MU_A = threading.Lock()
        MU_B = threading.Lock()

        def first():
            with MU_A:
                with MU_B:
                    pass

        def second():
            with MU_A:
                with MU_B:
                    pass
    """)
    assert not r011(findings), [f.render() for f in r011(findings)]


def test_r011_anchors_used_and_not_stale():
    """The new R011 anchors resolve against the shipped tree (the
    staleness pass accepts them) and every one is exercised."""
    entries, errs = load_allowlist(DEFAULT_ALLOWLIST)
    assert not errs, errs
    r011_entries = [e for e in entries if e.rule == "R011"]
    assert len(r011_entries) >= 4
    stale = check_allowlist_staleness(entries, [PKG_DIR],
                                      DEFAULT_ALLOWLIST)
    assert not stale, stale
    findings, errors = lint_paths([PKG_DIR])
    assert not errors
    apply_allowlist(findings, entries)
    unused = [e.render() for e in r011_entries if not e.used]
    assert not unused, f"unused R011 anchors: {unused}"


# ====================================================== R012 (resources)
def r012(findings):
    return [f for f in findings if f.rule == "R012"]


def test_r012_thread_without_join_vs_daemon(tmp_path):
    """Seed: a named, started thread nobody joins is a finding; the
    daemon spelling of the same thread is a deliberate non-finding."""
    findings = lint_snippet(tmp_path, """
        import threading

        def spawn(work):
            t = threading.Thread(target=work, name="leak")
            t.start()

        def background(work):
            t = threading.Thread(target=work, daemon=True)
            t.start()
    """)
    bad = r012(findings)
    assert len(bad) == 1, [f.render() for f in bad]
    assert "never released" in bad[0].message
    assert bad[0].func == "spawn"


def test_r012_open_outside_with_on_exception_edge(tmp_path):
    """Seed: file opened, a raising call, THEN the try/finally — the
    PR-10 shape with a plain fd instead of a profiler."""
    findings = lint_snippet(tmp_path, """
        def dump(path, payload):
            fh = open(path, "w")
            encoded = encode(payload)
            try:
                fh.write(encoded)
            finally:
                fh.close()
    """)
    bad = r012(findings)
    assert len(bad) == 1, [f.render() for f in bad]
    assert "can raise and skip the release" in bad[0].message


def test_r012_listener_registered_never_unregistered(tmp_path):
    findings = lint_snippet(tmp_path, """
        import jax

        def install(on_event):
            jax.monitoring.register_event_listener(on_event)
    """)
    bad = r012(findings)
    assert len(bad) == 1, [f.render() for f in bad]
    assert "listener registered" in bad[0].message


def test_r012_unbounded_float_keyed_jitted_cache(tmp_path):
    """Seed: the PR 14 _score_accum_fn bug — lru_cache(maxsize=None)
    over unannotated/float keys retaining one jitted program per model
    version forever. The int/bool-annotated twin is clean."""
    findings = lint_snippet(tmp_path, """
        import functools
        import jax

        @functools.lru_cache(maxsize=None)
        def accum_fn(lo, hi, bins):
            return jax.jit(lambda x: x * (hi - lo))

        @functools.lru_cache(maxsize=None)
        def accum_fn_keyed(bins: int, weighted: bool):
            return jax.jit(lambda x: x)

        @functools.lru_cache(maxsize=32)
        def accum_fn_bounded(lo, hi):
            return jax.jit(lambda x: x * (hi - lo))
    """)
    bad = r012(findings)
    assert len(bad) == 1, [f.render() for f in bad]
    assert bad[0].func == "accum_fn"
    assert "PR 14" in bad[0].message


def test_r012_unbounded_per_version_metric_series(tmp_path):
    findings = lint_snippet(tmp_path, """
        _SERIES = {}

        def record(version, value):
            series = _SERIES.setdefault(version, ScoreHistogram())
            series.add(value)
    """)
    bad = r012(findings)
    assert len(bad) == 1, [f.render() for f in bad]
    assert "no statically visible bound" in bad[0].message


def test_r012_pruned_program_cache_is_clean(tmp_path):
    """An eviction call anywhere in the module is the statically visible
    bound the checker wants."""
    findings = lint_snippet(tmp_path, """
        import jax

        _PROGRAM_CACHE = {}

        def program_for(rows):
            if rows not in _PROGRAM_CACHE:
                while len(_PROGRAM_CACHE) >= 32:
                    _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
                _PROGRAM_CACHE[rows] = jax.jit(lambda x: x)
            return _PROGRAM_CACHE[rows]
    """)
    assert not r012(findings), [f.render() for f in r012(findings)]


def test_r012_rung_keyed_series_is_clean(tmp_path):
    """Keys mapped through a rung/bucket ladder have a bounded domain
    even without an eviction call."""
    findings = lint_snippet(tmp_path, """
        _BY_RUNG = {}

        def window_for(rows):
            rung = rung_of(rows)
            if rung not in _BY_RUNG:
                _BY_RUNG[rung] = LatencyWindow()
            return _BY_RUNG[rung]
    """)
    assert not r012(findings), [f.render() for f in r012(findings)]


def test_r012_anchors_used_and_not_stale():
    """The R012 anchor resolves against the shipped tree and is
    exercised (the process-lifetime jax.monitoring listener latch)."""
    entries, errs = load_allowlist(DEFAULT_ALLOWLIST)
    assert not errs, errs
    r012_entries = [e for e in entries if e.rule == "R012"]
    assert 1 <= len(r012_entries) <= 8
    stale = check_allowlist_staleness(entries, [PKG_DIR],
                                      DEFAULT_ALLOWLIST)
    assert not stale, stale
    findings, errors = lint_paths([PKG_DIR])
    assert not errors
    apply_allowlist(findings, entries)
    unused = [e.render() for e in r012_entries if not e.used]
    assert not unused, f"unused R012 anchors: {unused}"


# ==================================================== knob-drift lint
def test_knobs_lint_package_is_clean():
    """Every tpu_* knob in config.PARAMS is read somewhere in the
    package AND documented in README.md — dead knobs and doc drift are
    findings (satellite 2)."""
    from lightgbm_tpu.analysis import knobs
    problems, found = knobs.check_knobs()
    assert not problems, problems
    assert len(found) > 30      # sanity: the parse actually saw PARAMS


# ================================================= aggregate all --json
def test_main_all_json_aggregate_schema(tmp_path, capsys):
    """`scripts/tpulint all --json` (satellite 3): one parseable object
    with per-stage exits/findings and a max-exit summary, over the
    jax-free stage subset."""
    import json
    from lightgbm_tpu.analysis.tpulint import main_all
    rc = main_all(["--json", "--only", "ast,resources,knobs"], PKG_DIR)
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(payload) == {"stages", "exit"}
    assert payload["exit"] == 0
    assert set(payload["stages"]) == {"ast", "resources", "knobs"}
    for stage in payload["stages"].values():
        assert stage["exit"] == 0
    assert isinstance(payload["stages"]["ast"]["findings"], list)
    assert isinstance(payload["stages"]["resources"]["findings"], list)
    assert payload["stages"]["knobs"]["report"]["problems"] == []
