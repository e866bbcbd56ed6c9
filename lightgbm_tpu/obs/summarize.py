"""Summarize flight-recorder / metrics JSONL: the Common::Timer::Print.

The reference prints a per-phase wall-time table at process exit when
built with ``USE_TIMETAG`` (``Common::Timer::Print``,
include/LightGBM/utils/log.h). Here the equivalent table is derived
offline from the observability artifacts a run leaves behind — a
``tpu_metrics_path`` stream, a flight-recorder dump, or both:

    scripts/obs run_metrics.jsonl flight_1234.jsonl
    scripts/obs --json run_metrics.jsonl
    scripts/obs drift serve_metrics.jsonl     # serving-quality view:
                                              # latest PSI flush + SLO
                                              # burn tail (obs/drift.py)

prints per-phase host time share (from a summary record, else summed
from a dump's ``span`` records), the per-iteration table (seconds and the
update's counters: dispatches, host syncs, bytes fetched), phase-keyed
compile totals and a dump's compile seconds by kind, persistent-cache
hit/miss, collective-program byte totals (when the run captured them via
LGBM_TPU_COMM_ACCOUNTING), and the tail of notable events (faults,
deadlines, restarts, swaps) — the post-mortem read of a dead run, or the
profile read of a healthy one.

This module is intentionally jax-free (plain json/os), so ``scripts/obs``
runs anywhere in milliseconds, including hosts without a backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

#: the update's counters an ``iteration`` record carries (obs/spans.py)
ITERATION_COUNTERS = ("dispatches", "host_syncs", "d2h_bytes")

#: event kinds surfaced in the "notable events" tail
NOTABLE = ("slow_iteration", "fault_fire", "deadline", "retry", "crash",
           "training_interrupted", "swap_failed", "worker_restart",
           "snapshot_corrupt", "straggler", "rank_missing",
           "drift_detected", "drift_cleared", "slo_burn",
           "slo_burn_cleared")


def _read_jsonl(path: str) -> List[Dict[str, Any]]:
    from .metrics import read_stream
    return read_stream(path)


def _kind(rec: Dict[str, Any]) -> str:
    # flight records type themselves with "event" and may carry a
    # PAYLOAD field named "kind" (fault_fire's fault kind); metrics
    # records type with "kind" and never have "event" — so "event"
    # must win the classification
    return str(rec.get("event") or rec.get("kind") or "")


def summarize(paths: Sequence[str]) -> Dict[str, Any]:
    """Aggregate one or more JSONL artifacts into a summary dict."""
    records: List[Dict[str, Any]] = []
    for p in paths:
        records.extend(_read_jsonl(p))

    phase_times: Dict[str, Dict[str, float]] = {}
    compiles: Optional[Dict[str, Any]] = None
    cache: Optional[Dict[str, Any]] = None
    collectives: Dict[str, Dict[str, Any]] = {}
    iters = 0
    iter_seconds = 0.0
    per_iteration: List[Dict[str, Any]] = []
    span_times: Dict[str, Dict[str, float]] = {}
    compile_seconds: Dict[str, float] = {}
    notable: List[Dict[str, Any]] = []
    spans_seen: List[str] = []
    dump_header: Optional[Dict[str, Any]] = None
    rank_stats: Optional[Dict[str, Any]] = None
    stragglers: List[Dict[str, Any]] = []

    for rec in records:
        k = _kind(rec)
        if k == "iteration":
            iters += 1
            iter_seconds += float(rec.get("seconds", 0.0) or 0.0)
            per_iteration.append(
                {key: rec.get(key) for key in
                 ("iteration", "seconds", "cpu_s", "phase_s")
                 + ITERATION_COUNTERS})
            if isinstance(rec.get("compiles"), dict):
                compiles = rec["compiles"]     # cumulative: keep the last
            if isinstance(rec.get("cache"), dict):
                cache = rec["cache"]
        elif k in ("summary", "mark"):
            if isinstance(rec.get("phase_times"), dict):
                phase_times = rec["phase_times"]
            if isinstance(rec.get("compiles"), dict):
                compiles = rec["compiles"]
            if isinstance(rec.get("cache"), dict):
                cache = rec["cache"]
            if isinstance(rec.get("spans_seen"), list):
                spans_seen = sorted(set(spans_seen)
                                    | set(rec["spans_seen"]))
        elif k == "span":
            slot = span_times.setdefault(
                str(rec.get("name")), {"seconds": 0.0, "count": 0})
            slot["seconds"] += float(rec["t1"]) - float(rec["t0"])
            slot["count"] += 1
        elif k == "compile":
            kind = str(rec.get("kind"))
            compile_seconds[kind] = compile_seconds.get(kind, 0.0) \
                + float(rec.get("seconds", 0.0) or 0.0)
        elif k == "collective_program":
            collectives[str(rec.get("key"))] = {
                "bytes": rec.get("bytes"), "total": rec.get("total")}
        elif k == "rank_stats":
            rank_stats = rec                   # cumulative-ish: keep last
        elif k == "straggler":
            stragglers.append(rec)
        elif k == "flight_dump":
            dump_header = rec
        if k in NOTABLE:
            notable.append(rec)

    # a run's summary record carries the whole run's table; a dump has
    # only what its ring still holds, and is the fallback
    phase_times = phase_times or span_times
    total_phase_s = sum(float(v.get("seconds", 0.0) or 0.0)
                        for v in phase_times.values()) or None
    return {
        "records": len(records),
        "iterations": iters,
        "iter_seconds_mean": (iter_seconds / iters) if iters else None,
        "per_iteration": per_iteration[-20:],
        "compile_seconds": compile_seconds,
        "phase_times": phase_times,
        "phase_total_seconds": total_phase_s,
        "rank_stats": rank_stats,
        "stragglers": stragglers[-20:],
        "compiles": compiles,
        "cache": cache,
        "collectives": collectives,
        "collective_bytes_total": sum(
            int(v.get("total") or 0) for v in collectives.values()) or None,
        "spans_seen": spans_seen,
        "notable": notable[-20:],
        "dump": dump_header,
    }


def _mark_index(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Last occurrence of each named ``mark`` record."""
    out: Dict[str, Dict[str, Any]] = {}
    for rec in records:
        if _kind(rec) == "mark" and rec.get("name"):
            out[str(rec["name"])] = rec
    return out


def _diff_compiles(a: Optional[Dict], b: Optional[Dict]) -> Dict[str, Any]:
    """b - a of two cumulative compile snapshots (phase-keyed)."""
    a, b = a or {}, b or {}

    def n(d, key):
        return int((d or {}).get(key, 0) or 0)

    phases = set((a.get("by_phase") or {})) | set((b.get("by_phase") or {}))
    by_phase = {}
    for p in sorted(phases):
        pa = (a.get("by_phase") or {}).get(p) or {}
        pb = (b.get("by_phase") or {}).get(p) or {}
        d = {"lowerings": n(pb, "lowerings") - n(pa, "lowerings"),
             "backend_compiles": (n(pb, "backend_compiles")
                                  - n(pa, "backend_compiles"))}
        if d["lowerings"] or d["backend_compiles"]:
            by_phase[p] = d
    return {"lowerings": n(b, "lowerings") - n(a, "lowerings"),
            "backend_compiles": (n(b, "backend_compiles")
                                 - n(a, "backend_compiles")),
            "by_phase": by_phase}


def bench_counters(path: str) -> Optional[Dict[str, Any]]:
    """Derive the BENCH-row counters from a metrics stream.

    Expects the bench marks ``warmup_start``/``warmup_end``/
    ``steady_end`` (each carrying a cumulative ``compiles``/``cache``
    snapshot). Returns None when the stream is missing or unmarked, so
    bench.py can fall back to its inline counters instead of recording a
    half-empty row."""
    if not path or not os.path.exists(path):
        return None
    records = _read_jsonl(path)
    marks = _mark_index(records)
    if not all(m in marks for m in ("warmup_start", "warmup_end",
                                    "steady_end")):
        return None
    w0, w1, s1 = (marks["warmup_start"], marks["warmup_end"],
                  marks["steady_end"])
    warm = _diff_compiles(w0.get("compiles"), w1.get("compiles"))
    steady = _diff_compiles(w1.get("compiles"), s1.get("compiles"))

    def cache_of(rec):
        c = rec.get("cache") or {}
        return {k: int(c.get(k, 0) or 0) for k in ("requests", "hits")}

    # cache counters over the WARMUP window, matching compile_events and
    # the inline warm_cache fallback — mixing windows would let a
    # steady-state compile skew the warm-round hits==requests comparison
    c0, c1 = cache_of(w0), cache_of(w1)
    requests = c1["requests"] - c0["requests"]
    hits = c1["hits"] - c0["hits"]
    return {
        "warmup_seconds": round(float(w1["t"]) - float(w0["t"]), 1),
        "compile_events": warm["lowerings"],
        "compile_events_by_phase": warm["by_phase"],
        "compile_events_steady": steady["lowerings"],
        "compile_cache": {"requests": requests, "hits": hits,
                          "misses": requests - hits},
    }


def _fmt_table(summary: Dict[str, Any]) -> str:
    lines: List[str] = []
    pt = summary["phase_times"]
    total = summary["phase_total_seconds"]
    lines.append(f"records: {summary['records']}  "
                 f"iterations: {summary['iterations']}"
                 + (f"  mean iter: {summary['iter_seconds_mean']:.4f}s"
                    if summary["iter_seconds_mean"] else ""))
    if pt:
        # wall clock at the host tick sites: dispatch time, except where
        # the site blocks on the device (flush_trees); spans nest, so the
        # shares do not add up to one
        lines.append("")
        lines.append(f"{'phase':<20} {'host_s':>10} {'share':>7} "
                     f"{'count':>8}")
        for name, v in sorted(pt.items(), key=lambda kv: -float(
                kv[1].get("seconds", 0) or 0)):
            sec = float(v.get("seconds", 0.0) or 0.0)
            lines.append(f"{name:<20} {sec:>10.3f} "
                         f"{(sec / total) if total else 0.0:>6.1%} "
                         f"{int(v.get('count', 0) or 0):>8}")
    rows = [r for r in summary.get("per_iteration") or []
            if any(r.get(c) is not None for c in ITERATION_COUNTERS)]
    if rows:
        lines.append("")
        # cpu_s: the CPU seconds the updating thread was given; phase_s:
        # the update's seconds by span, the three longest (a parent's
        # hold its children's: flush_trees holds step_wait)
        lines.append(f"{'iteration':>9} {'seconds':>10} {'cpu_s':>10} "
                     f"{'dispatches':>10} {'host_syncs':>10} "
                     f"{'d2h_bytes':>10}  phase_s")
        for r in rows:
            phases = sorted((r.get("phase_s") or {}).items(),
                            key=lambda kv: -float(kv[1]))[:3]
            lines.append(
                f"{r.get('iteration')!s:>9} "
                f"{float(r.get('seconds') or 0.0):>10.4f} "
                f"{float(r.get('cpu_s') or 0.0):>10.4f} "
                + " ".join(f"{int(r.get(c) or 0):>10}"
                           for c in ITERATION_COUNTERS)
                + "  " + " ".join(f"{n}={float(v):.4f}" for n, v in phases))
    rs = summary.get("rank_stats")
    if rs:
        lines.append("")
        lines.append(
            f"ranks: {rs.get('ranks_reporting')}/{rs.get('world')} "
            f"reporting  median {rs.get('median_s')}s  "
            f"p99 {rs.get('p99_s')}s  max {rs.get('max_s')}s "
            f"(rank {rs.get('max_rank')})  wait_max "
            f"{rs.get('wait_max_s')}s")
        if summary.get("stragglers"):
            for rec in summary["stragglers"][-5:]:
                lines.append(
                    f"  straggler: rank {rec.get('rank')} @ iteration "
                    f"{rec.get('iteration')} ({rec.get('slow_s')}s vs "
                    f"median {rec.get('rolling_median_s')}s)")
    comp = summary["compiles"]
    if comp:
        lines.append("")
        lines.append(f"compiles: {comp.get('lowerings', 0)} lowerings, "
                     f"{comp.get('backend_compiles', 0)} backend")
        for p, d in sorted((comp.get("by_phase") or {}).items()):
            lines.append(f"  {p:<18} {d.get('lowerings', 0):>4} lowerings "
                         f"{d.get('backend_compiles', 0):>4} backend")
    if summary.get("compile_seconds"):
        lines.append("compile seconds by kind (nested kinds overlap): "
                     + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
                         summary["compile_seconds"].items())))
    cache = summary["cache"]
    if cache:
        lines.append(f"compile cache: {cache.get('hits', 0)}/"
                     f"{cache.get('requests', 0)} hits")
    if summary["collectives"]:
        lines.append("")
        lines.append(f"collective programs "
                     f"({summary['collective_bytes_total']} bytes/step "
                     f"total):")
        for key, v in sorted(summary["collectives"].items()):
            lines.append(f"  {key:<24} {v.get('total')} bytes "
                         f"{json.dumps(v.get('bytes'), default=str)}")
    if summary["spans_seen"]:
        lines.append("")
        lines.append("spans seen: " + ", ".join(summary["spans_seen"]))
    if summary["dump"]:
        d = summary["dump"]
        lines.append("")
        lines.append(f"flight dump: reason={d.get('reason')!r} "
                     f"events={d.get('events')} dropped={d.get('dropped')}")
    if summary["notable"]:
        lines.append("")
        lines.append("notable events (tail):")
        for rec in summary["notable"]:
            k = _kind(rec)
            # drop only the field that typed the record: a flight
            # event's PAYLOAD "kind" (fault_fire's kill/hang) stays
            rest = {key: v for key, v in rec.items()
                    if key not in ("event", "t", "seq")
                    and not (key == "kind" and rec.get("event") is None)}
            lines.append(f"  {k}: {json.dumps(rest, default=str)}")
    return "\n".join(lines)


def _rank_of_dump(path: str, header: Optional[Dict[str, Any]]) -> int:
    """Rank of a flight dump: the header's rank field, else the
    ``_rank<k>`` filename tag, else 0."""
    if header is not None and header.get("rank") is not None:
        try:
            return int(header["rank"])
        except (TypeError, ValueError):
            pass
    import re
    m = re.search(r"_rank(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else 0


def merge_ranks(paths: Sequence[str]) -> List[Dict[str, Any]]:
    """Interleave rank-tagged flight dumps into ONE cross-rank timeline
    ordered by ``(time, source rank)`` — each record annotated with
    ``src_rank``, the rank whose dump it came from. A separate key on
    purpose: events like ``straggler``/``rank_missing`` carry a payload
    ``rank`` (the rank they are ABOUT), which the annotation must not
    clobber — rank 0's dump says rank 1 straggled. The post-mortem read
    of a pod: rank 1's fault fire lines up against rank 0's straggler
    flag and collective-deadline events in wall-clock order."""
    merged: List[Dict[str, Any]] = []
    for path in paths:
        records = _read_jsonl(path)
        header = records[0] if records \
            and _kind(records[0]) == "flight_dump" else None
        rank = _rank_of_dump(path, header)
        for rec in records:
            out = dict(rec)
            out["src_rank"] = rank
            merged.append(out)
    merged.sort(key=lambda r: (float(r.get("t", 0.0) or 0.0),
                               int(r.get("src_rank", 0)),
                               int(r.get("seq", 0) or 0)))
    return merged


def _fmt_merge(merged: List[Dict[str, Any]]) -> str:
    lines = []
    for rec in merged:
        k = _kind(rec)
        rest = {key: v for key, v in rec.items()
                if key not in ("kind", "event", "t", "seq", "src_rank")}
        lines.append(f"{float(rec.get('t', 0.0) or 0.0):>17.6f} "
                     f"r{rec.get('src_rank', 0)} {k:<22} "
                     f"{json.dumps(rest, default=str)}")
    return "\n".join(lines)


def merge_main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="obs merge",
        description="interleave rank-tagged flight dumps into one "
                    "cross-rank timeline ordered by (time, rank)")
    ap.add_argument("paths", nargs="+", help="rank-tagged dump files")
    ap.add_argument("--jsonl", action="store_true",
                    help="emit merged records as JSONL instead of a table")
    args = ap.parse_args(argv)
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"obs merge: no such file: {', '.join(missing)}",
              file=sys.stderr)
        return 2
    merged = merge_ranks(args.paths)
    if args.jsonl:
        for rec in merged:
            print(json.dumps(rec, default=str))
    else:
        print(_fmt_merge(merged))
    return 0


def drift_summary(paths: Sequence[str], top: int = 10) -> Dict[str, Any]:
    """Aggregate serving-quality records (``drift_flush`` / ``slo`` plus
    drift/SLO events) from metrics streams / flight dumps into one
    summary dict — the latest flush's PSI table, top-k drifted features,
    score drift, and the SLO burn-rate tail."""
    records: List[Dict[str, Any]] = []
    for p in paths:
        records.extend(_read_jsonl(p))
    # the same flush appears TWICE when given both the metrics stream
    # and a flight dump (the ring carries a summary twin of every
    # drift_flush): dedup by (version, flush), preferring the record
    # with the full psi map (the stream one) over the compact twin
    seen: Dict[tuple, Dict[str, Any]] = {}
    order: List[tuple] = []
    for rec in records:
        if _kind(rec) != "drift_flush":
            continue
        key = (rec.get("version"), rec.get("flush"))
        cur = seen.get(key)
        if cur is None:
            seen[key] = rec
            order.append(key)
        elif isinstance(rec.get("psi"), dict) \
                and not isinstance(cur.get("psi"), dict):
            seen[key] = rec
    flushes = [seen[k] for k in order]
    slo = [r for r in records if _kind(r) == "slo"]
    events = [r for r in records
              if _kind(r) in ("drift_detected", "drift_cleared",
                              "slo_burn", "slo_burn_cleared")]
    latest = flushes[-1] if flushes else None
    table: List[Dict[str, Any]] = []
    for rec in reversed(flushes):
        psi = rec.get("psi")
        if isinstance(psi, dict) and psi:
            klm = rec.get("kl") if isinstance(rec.get("kl"), dict) else {}
            drifted = set(rec.get("drifted") or ())
            table = [{"feature": k, "psi": float(v),
                      "kl": klm.get(k), "drifted": k in drifted}
                     for k, v in sorted(psi.items(),
                                        key=lambda kv: -float(kv[1]))]
            break
    return {
        "flushes": len(flushes),
        "latest": latest,
        "psi_table": table[:max(int(top), 1)],
        "drift_events": events[-20:],
        "slo_tail": slo[-8:],
    }


def _fmt_drift(s: Dict[str, Any]) -> str:
    lines: List[str] = []
    latest = s.get("latest")
    if latest is None:
        lines.append("no drift_flush records found (is "
                     "tpu_drift_flush_every armed and the stream/flight "
                     "dump from a serving run?)")
    else:
        lines.append(
            f"drift flushes: {s['flushes']}  latest: flush "
            f"#{latest.get('flush')} version={latest.get('version')!r} "
            f"window_rows={latest.get('window_rows')} "
            f"threshold={latest.get('threshold')}")
        sp = latest.get("score_psi")
        lines.append(f"score drift: psi="
                     f"{sp if sp is not None else '-'}"
                     + (" [DRIFTED]" if latest.get("score_drifted")
                        else ""))
        if s["psi_table"]:
            lines.append("")
            lines.append(f"{'feature':<24} {'psi':>10} {'kl':>10}  state")
            for row in s["psi_table"]:
                kl = row.get("kl")
                kls = f"{kl:>10.4f}" if kl is not None else f"{'-':>10}"
                lines.append(
                    f"{str(row['feature'])[:24]:<24} "
                    f"{row['psi']:>10.4f} {kls}"
                    f"  {'DRIFTED' if row['drifted'] else 'ok'}")
    if s["drift_events"]:
        lines.append("")
        lines.append("drift/SLO events (tail):")
        for rec in s["drift_events"]:
            rest = {k: v for k, v in rec.items()
                    if k not in ("event", "kind", "t", "seq")}
            lines.append(f"  {_kind(rec)}: {json.dumps(rest, default=str)}")
    if s["slo_tail"]:
        lines.append("")
        lines.append(f"{'good':>10} {'bad':>8} {'burn_5m':>9} "
                     f"{'burn_1h':>9}  alerting")
        for rec in s["slo_tail"]:
            lines.append(
                f"{int(rec.get('good_total', 0) or 0):>10} "
                f"{int(rec.get('bad_total', 0) or 0):>8} "
                f"{float(rec.get('burn_5m', 0) or 0):>9.3f} "
                f"{float(rec.get('burn_1h', 0) or 0):>9.3f}  "
                f"{bool(rec.get('alerting'))}")
    return "\n".join(lines)


def drift_main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="obs drift",
        description="latest serving drift flush: per-feature PSI table, "
                    "top drifted features, score drift, SLO burn-rate "
                    "tail (from tpu_metrics_path streams / flight dumps)")
    ap.add_argument("paths", nargs="+",
                    help="metrics-stream / flight-dump JSONL files")
    ap.add_argument("--top", type=int, default=10,
                    help="PSI table rows (default 10)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the summary as JSON instead of a table")
    args = ap.parse_args(argv)
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"obs drift: no such file: {', '.join(missing)}",
              file=sys.stderr)
        return 2
    s = drift_summary(args.paths, top=args.top)
    if args.as_json:
        print(json.dumps(s, indent=1, default=str))
    else:
        print(_fmt_drift(s))
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # subcommands ride in front of the legacy positional form
    # (`scripts/obs <files>` keeps summarizing, unchanged)
    if argv and argv[0] == "merge":
        return merge_main(argv[1:])
    if argv and argv[0] == "drift":
        return drift_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="obs", description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="metrics-stream / flight-dump JSONL files")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the summary as JSON instead of a table")
    args = ap.parse_args(argv)
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"obs: no such file: {', '.join(missing)}", file=sys.stderr)
        return 2
    summary = summarize(args.paths)
    if args.as_json:
        print(json.dumps(summary, indent=1, default=str))
    else:
        print(_fmt_table(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
