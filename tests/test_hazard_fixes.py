"""Regression tests for the ADVICE r5 hazard fixes (the tpulint seed
cases) + the bench backend-init retry."""
import importlib.util
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.engines.registry import validated_fused_block_env
from lightgbm_tpu.ops.compact import RowLayout
from lightgbm_tpu.ops.fused_split import fused_split
from lightgbm_tpu.parallel.comm_accounting import collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------- ADVICE #1: comm accounting
HLO = """\
ENTRY %main {
  %p = f32[16]{0} parameter(0)
  %ag = (f32[16]{0}, f32[128]{0}) all-gather-start(f32[16]{0} %p)
  %agd = f32[128]{0} all-gather-done((f32[16]{0}, f32[128]{0}) %ag)
  %ar = (f32[32]{0}, f32[32]{0}) all-reduce-start(f32[32]{0} %p2)
  %ard = f32[32]{0} all-reduce-done((f32[32]{0}, f32[32]{0}) %ar)
  %rs = f32[8]{0} reduce-scatter(f32[64]{0} %p3)
}
"""


def test_all_gather_start_counts_result_shape():
    """8-device all-gather: result is 8x the operand; bytes must reflect
    the gathered (result) payload, not the pre-transfer operand."""
    out = collective_bytes(HLO)
    assert out["all-gather-start"] == 128 * 4        # NOT 16 * 4
    assert out["all-reduce-start"] == 32 * 4         # operand == result
    assert out["reduce-scatter"] == 8 * 4
    assert out["count"] == 3                         # -done ops not counted
    assert out["total"] == 128 * 4 + 32 * 4 + 8 * 4


def test_collective_permute_start_counts_result_shape():
    hlo = ("%cp = (f32[64]{0}, f32[64]{0}) "
           "collective-permute-start(f32[64]{0} %x)")
    out = collective_bytes(hlo)
    assert out["collective-permute-start"] == 64 * 4


def test_reduce_scatter_start_counts_result_shape():
    """The psum_scatter path gone async (R005 extension seed): the
    reduce-scatter result is operand/num_devices — counting the operand
    would over-report 8x, and missing the kind entirely (the pre-fix
    inventory) reports 0."""
    hlo = ("%rs = (f32[64,8]{1,0}, f32[8,8]{1,0}) "
           "reduce-scatter-start(f32[64,8]{1,0} %x)\n"
           "%rsd = f32[8,8]{1,0} reduce-scatter-done("
           "(f32[64,8]{1,0}, f32[8,8]{1,0}) %rs)\n"
           "%aa = (f32[16]{0}, f32[16]{0}) all-to-all-start(f32[16]{0} %y)")
    out = collective_bytes(hlo)
    assert out["reduce-scatter-start"] == 8 * 8 * 4   # result, not operand
    assert out["all-to-all-start"] == 16 * 4
    assert out["count"] == 2                          # -done carries nothing


# ------------------------------------------- ADVICE #2: fused pad contract
def test_fused_split_raises_on_short_pad():
    layout = RowLayout(num_features=10, num_extra=2)
    C = layout.num_cols
    work = jnp.zeros((96, C), jnp.uint8)
    scratch = jnp.zeros((96, C), jnp.uint8)
    z = jnp.asarray(0, jnp.int32)
    with pytest.raises(ValueError, match="pad contract"):
        fused_split(work, scratch, jnp.asarray(1, jnp.int32), z,
                    jnp.asarray(64, jnp.int32), z, z, z, z, z, z,
                    jnp.zeros((8,), jnp.uint32), layout, 64,
                    block_size=64, num_rows=80)       # pad 16 < 64


# ------------------------------------------- ADVICE #3: env override guard
def test_env_override_rounded_to_32_multiple():
    assert validated_fused_block_env("100", 128, 384) == 96
    assert validated_fused_block_env("5", 128, 384) == 32
    assert validated_fused_block_env("256", 128, 384) == 256


def test_env_override_clamped_to_vmem_cap():
    """An oversize override must not recreate the VMEM blowup the scoped
    guard prevents (pre-fix: accepted raw)."""
    assert validated_fused_block_env("8192", 128, 384) == 384
    assert validated_fused_block_env("512", 2048, 64) == 64


# ------------------------------------------- ADVICE #4: docstring accuracy
def test_hist_contract_docstring_matches_implementation():
    """The flush as the code has it since PR 38 (``hist_matmuls`` through
    PR 32; one level at every stride through PR 37): a feature's bin row
    against a sublane iota, no lane gather or broadcast; the one-hot is
    the operand the MXU loads and the channels stream; at more than 64
    bins a bin is 64 hi + lo, the one-hot spans ``lo`` and the streamed
    rows are stacked by ``hi``; the cost is the push count."""
    src = open(os.path.join(
        REPO, "lightgbm_tpu", "ops", "fused_split.py")).read()
    assert "def hist_matmuls" not in src
    doc = re.search(r"def hist_contract.*?\"\"\"(.*?)\"\"\"", src,
                    re.DOTALL).group(1)
    assert "constant-index lane gather" not in doc
    assert "jnp.repeat" not in doc and "tile loads a row" not in doc
    assert "bin row" in doc and "sublane iota" in doc
    # two levels, and which operand is which
    assert "bin = 64 hi" in doc and "Two levels" in doc and "One level" in doc
    assert re.search(r"one-hot is the operand the MXU LOADS", doc)
    assert re.search(r"channels are the rows that STREAM", doc)
    assert "[16 G, bs]" in doc and "[8 G, F_pad x 64]" in doc
    # the cost is the transposed pushes, not the compiler's bundles
    assert "push count" in doc and "/ 2,048" in doc
    # and the code reads G off the stride: no key, no probe, no override
    body = src[src.index("def group_product"):src.index("def hist_flush")]
    assert "if G > 1" in body and "os.environ" not in body
    assert ("G, OW, F_pad, group_w = _hist_flush_shape(F, B, hist_layout)"
            in src)


# --------------------------------------------- bench backend-init retry
def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_retries_transient_backend_errors(monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)

    class FlakyJax:
        calls = 0

        def devices(self):
            FlakyJax.calls += 1
            if FlakyJax.calls < 3:
                raise RuntimeError("Unable to initialize backend 'tpu': "
                                   "UNAVAILABLE: connection reset")
            return ["tpu:0"]

    assert bench._init_backend_with_retry(FlakyJax()) == "tpu:0"
    assert FlakyJax.calls == 3


def test_bench_reraises_non_transient_errors(monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)

    class BrokenJax:
        calls = 0

        def devices(self):
            BrokenJax.calls += 1
            raise RuntimeError("no module named libtpu")

    with pytest.raises(RuntimeError, match="libtpu"):
        bench._init_backend_with_retry(BrokenJax())
    assert BrokenJax.calls == 1               # no pointless retries


def test_bench_gives_up_after_transient_attempts(monkeypatch):
    bench = _load_bench()
    sleeps = []
    monkeypatch.setattr(bench.time, "sleep", sleeps.append)

    class DownJax:
        calls = 0

        def devices(self):
            DownJax.calls += 1
            raise RuntimeError("Unable to initialize backend 'tpu'")

    with pytest.raises(RuntimeError, match="Unable to initialize"):
        bench._init_backend_with_retry(DownJax())
    assert DownJax.calls == 5                 # hardened round-6 default
    assert sleeps == [5.0, 10.0, 20.0, 40.0]  # exponential backoff


def test_bench_retries_enumeration_failures(monkeypatch):
    """The r05 gap: device ENUMERATION died on a gRPC connect error the
    init retry never matched, and an empty device list slipped through —
    both now retry through the same loop."""
    bench = _load_bench()
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)

    class EnumFlaky:
        calls = 0

        def devices(self):
            EnumFlaky.calls += 1
            if EnumFlaky.calls == 1:
                raise RuntimeError("failed to connect to all addresses")
            if EnumFlaky.calls == 2:
                return []                     # worker mid-restart
            return ["tpu:0"]

    assert bench._init_backend_with_retry(EnumFlaky()) == "tpu:0"
    assert EnumFlaky.calls == 3


def test_bench_refuses_cpu_backend_without_rehearsal_flag(monkeypatch):
    """A bench that finds no chip fails: on the CPU backend every stage
    exits non-zero before measuring or recording anything, unless the
    command line asks for the rehearsal — and a CPU device reached after a
    retry is refused just the same."""
    import jax
    bench = _load_bench()
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setattr(bench, "_record_shape", lambda *a: pytest.fail(
        "recorded a row on the CPU backend"))
    for var in ("BENCH_HIST_MICRO", "BENCH_PREDICT", "BENCH_SERVING",
                "BENCH_RANKING"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()                       # the default (train) stage
    assert exc.value.code not in (0, None)
    assert "--cpu-rehearsal" in str(exc.value.code)

    class CpuAfterRetry:
        __name__ = "flaky"
        calls = 0

        def devices(self):
            CpuAfterRetry.calls += 1
            if CpuAfterRetry.calls == 1:
                raise RuntimeError("Unable to initialize backend 'tpu'")
            return jax.devices()

    with pytest.raises(SystemExit):
        bench._require_device(CpuAfterRetry())
    assert CpuAfterRetry.calls == 2
    monkeypatch.setattr(bench.sys, "argv", ["bench.py", "--cpu-rehearsal"])
    assert bench._require_device(jax).platform == "cpu"


def test_bench_failure_stub_recorded(monkeypatch, tmp_path):
    """An unrecoverable failure emits the structured stub row (value null
    + error inline) AND records it in BENCH_SHAPES.json, so the BENCH_r0x
    row is never silently absent."""
    import json as _json
    bench = _load_bench()
    rec = tmp_path / "BENCH_SHAPES.json"
    monkeypatch.setattr(bench.os.path, "dirname", lambda p: str(tmp_path))
    out = []
    monkeypatch.setattr("builtins.print", out.append)
    bench._emit_failure_stub("train", RuntimeError("backend never up"))
    row = _json.loads(out[-1])
    assert row["value"] is None
    assert "backend never up" in row["error"]
    recorded = _json.loads(rec.read_text())["last_failure"]
    assert recorded["stage"] == "train"
    assert recorded["error_type"] == "RuntimeError"
