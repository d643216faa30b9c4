#!/usr/bin/env python
"""Bench-history regression gate over BENCH_LOCAL.jsonl.

For every (task, backend) series in the persisted bench log, compare
the NEWEST record against the trailing history (the median of the
earlier records' throughput): exit 1 when the newest throughput drops
more than ``--threshold`` percent below that median, or when the
newest record's roofline ``bound`` category flips (compute ↔ memory)
relative to the previous record of the same series — a bound flip
means the kernel moved to the other side of the ridge point, which is
a perf-structure change worth a human look even when raw throughput
held.

Throughput is whichever of THROUGHPUT_KEYS the record carries (tasks
measure different things: row-epochs/s for trainers, cells/s for the
histogram kernels, sustained QPS for serving, speedup ratios for the
DAG). Series with fewer than --min-history trailing records are
reported but never fail the gate — one data point is not a baseline.

Standing caveat (ROADMAP "Perf-claim caveat"): BENCH_LOCAL.jsonl is
not committed — it exists only where someone ran `bench.py` — so this
gate runs as an ADVISORY pass in tools/lint.sh: it prints findings
without failing lint (and says so when the log is absent). Run it
directly (exit code matters then) after a bench run on hardware.

    python tools/bench_regress.py [--log BENCH_LOCAL.jsonl]
                                  [--threshold 20] [--min-history 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# per-record throughput, first match wins (bigger = better for all)
THROUGHPUT_KEYS = (
    "row_epochs_per_sec", "row_trees_per_sec", "cells_per_sec",
    "rows_per_s", "qps_sustained", "stream_train_rows_per_s",
    "sens_col_rows_per_sec", "nn_row_epochs_per_sec", "dag_speedup",
    "speedup", "scores_per_sec",
)


def _throughput(rec: Dict) -> Optional[Tuple[str, float]]:
    for key in THROUGHPUT_KEYS:
        v = rec.get(key)
        if isinstance(v, (int, float)) and v > 0:
            return key, float(v)
    return None


def _fleet_p99(rec: Dict, cls: str) -> Optional[float]:
    by_class = rec.get("p99_ms_by_class")
    if isinstance(by_class, dict):
        v = by_class.get(cls)
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def _bound(rec: Dict, key: str = "roofline") -> Optional[str]:
    roof = rec.get(key)
    if isinstance(roof, dict):
        b = roof.get("bound")
        return str(b) if b else None
    return None


def load_series(path: str) -> Dict[Tuple[str, str], List[Dict]]:
    series: Dict[Tuple[str, str], List[Dict]] = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            backend = str(rec.get("backend", "?"))
            probe = rec.get("probe")
            if isinstance(probe, dict) and probe.get("fallback_reason"):
                # a record stamped with a probe fallback ran somewhere
                # it did not intend to (probe timeout → cpu): give it
                # its own series so it never dilutes — or trips — the
                # genuine hardware trend
                backend += "+fallback"
            key = (str(rec.get("task", "?")), backend)
            series.setdefault(key, []).append(rec)
    for recs in series.values():
        recs.sort(key=lambda r: r.get("ts", 0.0))
    return series


def check(path: str, threshold_pct: float, min_history: int) -> int:
    series = load_series(path)
    if not series:
        print(f"bench_regress: no records in {path}")
        return 0
    findings: List[str] = []
    for (task, backend), recs in sorted(series.items()):
        newest, history = recs[-1], recs[:-1]
        tp = _throughput(newest)
        label = f"{task}/{backend}"
        # continuous-refresh records (bench --task refresh) carry no
        # throughput key; their gates are absolute invariants, checked
        # BEFORE the throughput skip: the hot in-place swap must stay
        # cheaper than the evict+re-warm fallback it replaces, must
        # never recompile, and only guardrail-promoted challengers may
        # appear in a published record
        if task == "refresh":
            sw, rw = newest.get("swap_s"), newest.get("rewarm_s")
            if isinstance(sw, (int, float)) and \
                    isinstance(rw, (int, float)) and sw > rw:
                findings.append(
                    f"{label}: swap_s {sw:.4g} exceeds rewarm_s "
                    f"{rw:.4g} — the in-place swap lost to the "
                    "evict+re-warm fallback")
            scm = newest.get("swap_compile_misses")
            if isinstance(scm, (int, float)) and scm > 0:
                findings.append(
                    f"{label}: swap_compile_misses {scm:g} — the hot "
                    "swap recompiled resident executables")
            gr = newest.get("guardrail")
            if isinstance(gr, dict) and gr.get("decision") != "promote":
                findings.append(
                    f"{label}: guardrail decision "
                    f"{gr.get('decision')!r} in a published refresh "
                    "record — only promoted runs belong in the log")
        # live-promotion records (bench --task canary): two absolute
        # invariants first — a live cycle that failed even ONE client
        # request broke the headline promise (the primary never stops
        # serving; canary errors fall back, rollback just switches
        # routing off), and only guardrail-promoted live verdicts
        # belong in a published record. Rollback recovery latency
        # (breach verdict → incumbent re-pinned and serving) is
        # lower-is-better, ceilinged vs its trailing median below
        # like the ingest breach latency.
        if task == "canary":
            fr = newest.get("failed_requests")
            if isinstance(fr, (int, float)) and fr > 0:
                findings.append(
                    f"{label}: failed_requests {fr:g} — a live "
                    "promotion cycle dropped client requests")
            pv = newest.get("promote_verdict")
            if isinstance(pv, dict) and pv.get("decision") != "promote":
                findings.append(
                    f"{label}: promote_verdict "
                    f"{pv.get('decision')!r} in a published canary "
                    "record — only live-promoted runs belong in the "
                    "log")
            rr = newest.get("rollback_recovery_s")
            if isinstance(rr, (int, float)):
                hv = sorted(
                    float(r["rollback_recovery_s"]) for r in history
                    if isinstance(r.get("rollback_recovery_s"),
                                  (int, float)))
                if len(hv) >= min_history:
                    median = hv[len(hv) // 2]
                    ceil = median * (1.0 + threshold_pct / 100.0)
                    if rr > ceil:
                        findings.append(
                            f"{label}: rollback_recovery_s {rr:.4g} "
                            f"is {100.0 * (rr - median) / median:.1f}%"
                            f" above the trailing median {median:.4g}"
                            f" (threshold {threshold_pct:.0f}%)")
        # streaming-ingest records: append throughput rides the generic
        # rows_per_s gate and the replay verdict the generic
        # bitwise_identical gate below; breach-detection latency
        # (append → drift breach off a committed window) is
        # lower-is-better, ceilinged vs its trailing median like the
        # fleet p99s
        # tree-serving records (bench --task serving_tree): rows/s
        # rides the generic throughput gate and the per-size p99s the
        # generic p99_ms_by_class gate below; two absolute invariants
        # are checked here — the steady-state serve loop must never
        # recompile, and on the accelerator the fused Pallas ensemble
        # kernel must beat the interpretive bin+walk path it replaced
        # (CPU records are exempt: there the kernel runs in Pallas
        # interpret mode, which validates plumbing, not speed)
        if task == "serving_tree":
            ccm = newest.get("compile_cache_misses_steady")
            if isinstance(ccm, (int, float)) and ccm > 0:
                findings.append(
                    f"{label}: compile_cache_misses_steady {ccm:g} — "
                    "the tree-serving shape-bucket discipline leaked "
                    "a shape")
            fs = newest.get("fused_speedup")
            if backend == "tpu" and isinstance(fs, (int, float)) \
                    and fs < 1.0:
                findings.append(
                    f"{label}: fused_speedup {fs:.3f} < 1 — the fused "
                    "ensemble kernel lost to the xla bin+walk path "
                    "it replaced")
        if task == "ingest":
            bl = newest.get("breach_latency_s")
            if isinstance(bl, (int, float)):
                hv = sorted(
                    float(r["breach_latency_s"]) for r in history
                    if isinstance(r.get("breach_latency_s"),
                                  (int, float)))
                if len(hv) >= min_history:
                    median = hv[len(hv) // 2]
                    ceil = median * (1.0 + threshold_pct / 100.0)
                    if bl > ceil:
                        findings.append(
                            f"{label}: breach_latency_s {bl:.4g} is "
                            f"{100.0 * (bl - median) / median:.1f}% "
                            f"above the trailing median {median:.4g} "
                            f"(threshold {threshold_pct:.0f}%)")
        if tp is None:
            print(f"  {label}: no throughput key — skipped")
            continue
        key, value = tp
        hist_vals = [v for _, v in
                     filter(None, (_throughput(r) for r in history))]
        if len(hist_vals) < min_history:
            print(f"  {label}: {key}={value:.4g} — only "
                  f"{len(hist_vals)} trailing record(s), no baseline")
        else:
            hist_vals.sort()
            median = hist_vals[len(hist_vals) // 2]
            floor = median * (1.0 - threshold_pct / 100.0)
            delta = 100.0 * (value - median) / median
            if value < floor:
                findings.append(
                    f"{label}: {key} {value:.4g} is {-delta:.1f}% below "
                    f"the trailing median {median:.4g} "
                    f"(threshold {threshold_pct:.0f}%)")
            else:
                print(f"  {label}: {key}={value:.4g} "
                      f"({delta:+.1f}% vs median of {len(hist_vals)})")
        nb, pb = _bound(newest), next(
            (_bound(r) for r in reversed(history) if _bound(r)), None)
        if nb and pb and nb != pb:
            findings.append(
                f"{label}: roofline bound flipped {pb} → {nb} "
                "(crossed the ridge point — verify intentional)")
        # side-by-side records (gbt_stream) carry a second roofline for
        # the comparison mode — gate its bound the same way
        nhb = _bound(newest, "host_roofline")
        phb = next((_bound(r, "host_roofline") for r in reversed(history)
                    if _bound(r, "host_roofline")), None)
        if nhb and phb and nhb != phb:
            findings.append(
                f"{label}: host-tier roofline bound flipped "
                f"{phb} → {nhb} (comparison mode crossed the ridge)")
        # on the accelerator the device-resident state tier beating the
        # host tier IS the perf structure under test; losing it is a
        # regression even when headline throughput held. (CPU records
        # are exempt — both tiers live in host memory there.)
        sp = newest.get("resident_speedup")
        if backend == "tpu" and isinstance(sp, (int, float)) and sp < 1.0:
            findings.append(
                f"{label}: resident_speedup {sp:.2f} < 1 — the "
                "device-resident state tier lost to the host tier")
        # fleet records: per-priority-class p99 is lower-is-better
        # (the generic throughput gate above covers qps_sustained),
        # and the shed rate must not creep — both vs trailing medians,
        # advisory below --min-history like everything else
        if isinstance(newest.get("p99_ms_by_class"), dict):
            for cls in sorted(newest["p99_ms_by_class"]):
                nv = _fleet_p99(newest, cls)
                hv = sorted(v for v in (_fleet_p99(r, cls)
                                        for r in history)
                            if v is not None)
                if nv is None or len(hv) < min_history:
                    continue
                median = hv[len(hv) // 2]
                ceil = median * (1.0 + threshold_pct / 100.0)
                if nv > ceil:
                    findings.append(
                        f"{label}: p99_ms_by_class[{cls}] {nv:.4g} is "
                        f"{100.0 * (nv - median) / median:.1f}% above "
                        f"the trailing median {median:.4g} "
                        f"(threshold {threshold_pct:.0f}%)")
        sr = newest.get("shed_rate")
        if isinstance(sr, (int, float)):
            hv = sorted(float(r["shed_rate"]) for r in history
                        if isinstance(r.get("shed_rate"), (int, float)))
            if len(hv) >= min_history:
                median = hv[len(hv) // 2]
                # absolute headroom too: a 0 → 0.05 move shouldn't trip
                ceil = max(median * (1.0 + threshold_pct / 100.0),
                           median + 0.05)
                if sr > ceil:
                    findings.append(
                        f"{label}: shed_rate {sr:.4g} exceeds the "
                        f"trailing median {median:.4g} by more than "
                        f"{threshold_pct:.0f}% — low-priority traffic "
                        "is being shed harder than history")
        # pod-scale data plane (dist_stats): scaling efficiency has an
        # ABSOLUTE acceptance floor (0.7 at 2 hosts, ISSUE-14) on top
        # of the usual newest-vs-trailing-median gate, and the bitwise
        # parity verdict is a hard invariant, not a trend
        eff = newest.get("scaling_efficiency")
        if isinstance(eff, (int, float)):
            if eff < 0.7:
                findings.append(
                    f"{label}: scaling_efficiency {eff:.3f} below the "
                    "0.7 acceptance floor — the sharded data plane is "
                    "not splitting the work")
            hv = sorted(
                float(r["scaling_efficiency"]) for r in history
                if isinstance(r.get("scaling_efficiency"), (int, float)))
            if len(hv) >= min_history:
                median = hv[len(hv) // 2]
                floor = median * (1.0 - threshold_pct / 100.0)
                if eff < floor:
                    findings.append(
                        f"{label}: scaling_efficiency {eff:.3f} is "
                        f"{100.0 * (median - eff) / median:.1f}% below "
                        f"the trailing median {median:.3f} "
                        f"(threshold {threshold_pct:.0f}%)")
        sl = newest.get("slice")
        if isinstance(sl, dict):
            # multi-device pipeline records carry the sliced-vs-
            # timeshared A/B block: disjoint-slice concurrency must
            # never lose to the sequential schedule it replaces.
            # TPU records only — on one physical CPU the fake devices
            # share cores, so overlap is contention-bound and the
            # speedup hovers around 1 (CPU exempt, like fused_speedup)
            ss = sl.get("sliced_speedup")
            if backend == "tpu" and isinstance(ss, (int, float)) \
                    and ss < 1.0:
                findings.append(
                    f"{label}: sliced_speedup {ss:.2f} < 1 — device-"
                    "slice leasing lost to the timeshared sequential "
                    "schedule")
        if newest.get("bitwise_identical") is False:
            findings.append(
                f"{label}: bitwise_identical=false — sharded output "
                "diverged from the single-host run")
    if findings:
        print(f"bench_regress: {len(findings)} finding(s) in {path}:",
              file=sys.stderr)
        for f_ in findings:
            print(f"  REGRESSION {f_}", file=sys.stderr)
        return 1
    print(f"bench_regress: {len(series)} series clean in {path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--log",
                    default=os.path.join(REPO, "BENCH_LOCAL.jsonl"))
    ap.add_argument("--threshold", type=float, default=20.0,
                    help="percent drop vs trailing median that fails")
    ap.add_argument("--min-history", type=int, default=2,
                    help="trailing records required to form a baseline")
    args = ap.parse_args(argv)
    if not os.path.exists(args.log):
        print(f"bench_regress: {args.log} absent — nothing to gate")
        return 0
    return check(args.log, args.threshold, args.min_history)


if __name__ == "__main__":
    sys.exit(main())
