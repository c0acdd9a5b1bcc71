"""``dprf report SESSION``: one-shot performance report from session
artifacts alone.

Reads the journal family a run leaves behind -- ``<session>`` (job
identity + per-job records), ``<session>.trace.jsonl`` (lifecycle
spans), ``<session>.telemetry.jsonl`` (periodic registry
snapshots) -- and renders what a perf post-mortem needs without a
live coordinator: throughput, the host's hit verification, device
busy fraction per worker, compile-cache behavior, pipeline depth,
and per-job fair-share actual-vs-weight.
"""

from __future__ import annotations

import os
from typing import Optional

from dprf_tpu.telemetry.snapshot import load_snapshots, telemetry_path
from dprf_tpu.telemetry.trace import load_trace, trace_path


def _pct(vals: list, q: float) -> float:
    """Nearest-rank percentile of a sorted list."""
    if not vals:
        return 0.0
    i = min(len(vals) - 1, max(0, int(round(q * (len(vals) - 1)))))
    return vals[i]


def _metric_values(snapshot: Optional[dict], name: str) -> list:
    if not snapshot:
        return []
    m = (snapshot.get("metrics") or {}).get(name)
    if not isinstance(m, dict):
        return []
    return m.get("values") or []


def _counter_total(snapshot, name: str, **labels) -> float:
    total = 0.0
    for v in _metric_values(snapshot, name):
        lv = v.get("labels") or {}
        if all(lv.get(k) == val for k, val in labels.items()):
            total += float(v.get("value") or 0.0)
    return total


def _verify_stats(spans: list) -> Optional[dict]:
    """{count, p50_s, p95_s, total_s} of the host's verification of
    reported hits, from every hit batch's ``hit_verify`` span; None
    for a run that verified none."""
    durs = sorted(float(s.get("dur", 0.0)) for s in spans
                  if s.get("name") == "hit_verify")
    if not durs:
        return None
    return {"count": len(durs),
            "p50_s": round(_pct(durs, 0.50), 6),
            "p95_s": round(_pct(durs, 0.95), 6),
            "total_s": round(sum(durs), 6)}


def _busy_by_worker(spans: list) -> dict:
    """worker -> busy fraction over its own active span: union
    coverage / (first sweep start .. last sweep end) -- the offline
    form of the live dprf_device_busy_fraction gauge, same union-hole
    math as tools/trace_overlap.py."""
    from dprf_tpu.telemetry.trace import overlap_report
    rep = overlap_report(spans)
    sweeps_by_proc: dict = {}
    for s in spans:
        if s.get("name") == "sweep":
            sweeps_by_proc.setdefault(str(s.get("proc")), []).append(s)
    out = {}
    for proc, w in rep["workers"].items():
        sw = sweeps_by_proc.get(proc, [])
        if not sw:
            continue
        t0 = min(float(s.get("ts", 0.0)) for s in sw)
        t1 = max(float(s.get("ts", 0.0)) + float(s.get("dur", 0.0))
                 for s in sw)
        span = t1 - t0
        if span <= 0:
            out[proc] = 1.0
            continue
        out[proc] = round(max(0.0, span - w["idle_s"]) / span, 4)
    return out


def _throughput(spans: list, snapshot: Optional[dict]) -> dict:
    """H/s two ways: swept keyspace over the sweep-span wall window
    (trace-derived), and the candidates counter over the snapshot's
    elapsed time (telemetry-derived)."""
    sw = [s for s in spans if s.get("name") == "sweep"]
    out: dict = {"trace_hs": None, "telemetry_hs": None,
                 "candidates": 0}
    lengths = [int((s.get("attrs") or {}).get("length") or 0)
               for s in sw]
    if sw and sum(lengths) > 0:
        t0 = min(float(s.get("ts", 0.0)) for s in sw)
        t1 = max(float(s.get("ts", 0.0)) + float(s.get("dur", 0.0))
                 for s in sw)
        if t1 > t0:
            out["trace_hs"] = sum(lengths) / (t1 - t0)
        out["candidates"] = sum(lengths)
    if snapshot:
        cands = _counter_total(snapshot,
                               "dprf_candidates_hashed_total")
        elapsed = float(snapshot.get("elapsed_s") or 0.0)
        if cands and elapsed > 0:
            out["telemetry_hs"] = cands / elapsed
            out["candidates"] = max(out["candidates"], int(cands))
    return out


def _health_section(session_path: str, journal) -> Optional[dict]:
    """Fleet health post-mortem (ISSUE 10): fold the session's
    ``.alerts.jsonl`` transition stream and the journal's
    ``worker_health`` records into fired-per-rule counts, the alerts
    that never resolved, and each worker's final state.  None when
    the session left neither artifact (pre-health sessions)."""
    from dprf_tpu.telemetry.alerts import alerts_path, load_alerts
    events = load_alerts(alerts_path(session_path))
    health_events = (journal.health_events or []) if journal else []
    if not events and not health_events:
        return None
    fired: dict = {}
    last_state: dict = {}    # (rule, label key) -> last event
    for e in events:
        key = (str(e.get("rule")),
               tuple(sorted((e.get("labels") or {}).items())))
        last_state[key] = e
        if e.get("state") == "firing":
            fired[key[0]] = fired.get(key[0], 0) + 1
    # only FIRING counts as unresolved: a trailing "pending" event
    # usually means the condition cleared before the sustain window
    # (the engine drops those silently), and reporting it would be a
    # false post-mortem signal
    unresolved = sorted({
        f"{k[0]}({','.join(str(v) for _, v in k[1])})"
        if k[1] else k[0]
        for k, e in last_state.items()
        if e.get("state") == "firing"})
    workers: dict = {}
    for h in health_events:
        w = h.get("worker")
        if w is not None:
            workers[str(w)] = str(h.get("to"))
    return {"alert_events": len(events),
            "fired": fired,
            "unresolved": unresolved,
            "worker_transitions": len(health_events),
            "workers": workers}


def _memory_section(snapshot: Optional[dict]) -> Optional[dict]:
    """Device memory & program costs (ISSUE 13), reconstructed from
    the session's telemetry snapshots alone: the HBM gauges the
    devstats poller wrote (absent on backends without memory stats),
    the per-program peak-bytes gauge, and the analyzed-vs-hand
    roofline divergence cross-check.  None when the session recorded
    none of them (pre-introspection sessions)."""
    devices = {}
    for name, field in (("dprf_hbm_bytes_in_use", "in_use"),
                        ("dprf_hbm_bytes_limit", "limit"),
                        ("dprf_hbm_bytes_peak", "peak")):
        for v in _metric_values(snapshot, name):
            dev = (v.get("labels") or {}).get("device", "?")
            devices.setdefault(dev, {})[field] = int(
                v.get("value") or 0)
    programs = []
    for v in _metric_values(snapshot, "dprf_program_peak_bytes"):
        lv = v.get("labels") or {}
        programs.append({"engine": lv.get("engine", "?"),
                         "attack": lv.get("attack", "?"),
                         "peak_bytes": int(v.get("value") or 0)})
    programs.sort(key=lambda p: (p["engine"], p["attack"]))
    divergence = {}
    for v in _metric_values(snapshot, "dprf_roofline_model_divergence"):
        eng = (v.get("labels") or {}).get("engine", "?")
        divergence[eng] = round(float(v.get("value") or 0.0), 3)
    if not devices and not programs and not divergence:
        return None
    return {"devices": devices, "programs": programs,
            "model_divergence": divergence}


def _profile_section(journal) -> Optional[list]:
    """Kernel-profile captures (ISSUE 15): the ``{"type":
    "profile"}`` summaries the serve plane journaled when workers
    pushed their on-demand / alert-triggered capture windows.  None
    when the session recorded none (pre-profiling sessions, or
    nothing ever fired)."""
    records = (journal.profiles or []) if journal else []
    if not records:
        return None
    out = []
    for r in records:
        s = r.get("summary") or {}
        out.append({"worker": str(r.get("worker", "?")),
                    "trigger": s.get("trigger"),
                    "ts": s.get("ts"),
                    "engine": s.get("engine"),
                    "device_s": s.get("device_s"),
                    "fractions": s.get("fractions"),
                    "phases": s.get("phases"),
                    "top_ops": (s.get("top_ops") or [])[:5],
                    "divergence": s.get("divergence"),
                    "error": s.get("error")})
    return out


def _coverage_section(session_path: str) -> Optional[dict]:
    """Coverage audit summary (ISSUE 19): the offline auditor's
    per-job fraction / overlap / gap / digest-match rows plus its
    verdict, so the perf report answers "did we actually try
    everything?" next to "how fast?".  None when the auditor finds no
    artifacts (the full story lives in ``dprf audit``)."""
    from dprf_tpu.perfreport.audit import build_audit
    doc = build_audit(session_path)
    if doc is None:
        return None
    jobs = [{"job": j["job"],
             "fraction": j["fraction"],
             "gap_total": j["gap_total"],
             "overlap": j["trace_overlap"],
             "digest_match": j["digest_match"],
             "hit_dupes": j["hit_dupes"]}
            for j in doc["jobs"]]
    return {"verdict": doc["verdict"], "jobs": jobs}


def _fair_share(spans: list, journal) -> list:
    """Per-job lease share vs fair-share weight, from the lease spans
    and the journal's job records (the default job's priority is 1
    unless journaled otherwise)."""
    leases: dict = {}
    for s in spans:
        if s.get("name") != "lease":
            continue
        jid = (s.get("attrs") or {}).get("job")
        if jid is not None:
            leases[str(jid)] = leases.get(str(jid), 0) + 1
    if not leases:
        return []
    prio = {}
    if journal is not None:
        for jid, rec in (journal.jobs or {}).items():
            try:
                prio[str(jid)] = max(1, int(rec.get("priority") or 1))
            except (TypeError, ValueError):
                prio[str(jid)] = 1
    total = sum(leases.values())
    weight_total = sum(prio.get(j, 1) for j in leases)
    out = []
    for jid in sorted(leases):
        w = prio.get(jid, 1)
        out.append({"job": jid, "leases": leases[jid],
                    "actual_share": round(leases[jid] / total, 4),
                    "weight_share": round(w / weight_total, 4),
                    "priority": w})
    return out


def build_report(session_path: str) -> Optional[dict]:
    """The machine-readable report, or None when the session left no
    artifacts at all."""
    from dprf_tpu.runtime.session import SessionJournal
    spans = load_trace(trace_path(session_path))
    snaps = load_snapshots(telemetry_path(session_path))
    journal = (SessionJournal.load(session_path)
               if os.path.exists(session_path) else None)
    if not spans and not snaps and journal is None:
        return None
    last = snaps[-1] if snaps else None
    engine = (journal.spec.get("engine") if journal
              and journal.spec else None)
    thr = _throughput(spans, last)
    rate = thr.get("trace_hs") or thr.get("telemetry_hs")
    hits = _counter_total(last, "dprf_compile_cache_hits_total")
    misses = _counter_total(last, "dprf_compile_cache_misses_total")
    depth_vals = _metric_values(last, "dprf_worker_pipeline_depth")
    sweeps = [s for s in spans if s.get("name") == "sweep"]
    return {
        "session": session_path,
        "engine": engine,
        "spans": len(spans),
        "units": len(sweeps),
        "throughput": {
            "hs": rate,
            "trace_hs": thr["trace_hs"],
            "telemetry_hs": thr["telemetry_hs"],
            "candidates": thr["candidates"],
            # the gauge the run itself published: only the process
            # that held the chip knows which chip's band applies
            "roofline_frac": next(
                (v.get("value") for v in
                 _metric_values(last, "dprf_roofline_frac")
                 if (v.get("labels") or {}).get("engine") == engine
                 and v.get("value")), None),
        },
        "verify": _verify_stats(spans),
        "busy": _busy_by_worker(spans),
        "compile_cache": {
            "hits": int(hits), "misses": int(misses),
            "hit_rate": (round(hits / (hits + misses), 4)
                         if hits + misses else None),
        },
        "pipeline_depth": (float(depth_vals[-1]["value"])
                           if depth_vals else None),
        "fair_share": _fair_share(spans, journal),
        "coverage": _coverage_section(session_path),
        "health": _health_section(session_path, journal),
        "memory": _memory_section(last),
        "profiles": _profile_section(journal),
    }


def _fmt_hs(v: Optional[float]) -> str:
    if v is None:
        return "n/a"
    for unit, div in (("GH/s", 1e9), ("MH/s", 1e6), ("kH/s", 1e3)):
        if v >= div:
            return f"{v / div:,.2f} {unit}"
    return f"{v:,.0f} H/s"


def render_report(doc: dict) -> str:
    """The human half: a sectioned text report (stdout of ``dprf
    report``; CI uploads it as an artifact)."""
    lines = [f"dprf report — {doc['session']}",
             f"engine {doc.get('engine') or '?'} | "
             f"{doc['units']} units | "
             f"{doc['spans']} spans"]
    thr = doc["throughput"]
    roof = thr.get("roofline_frac")
    lines.append("")
    lines.append("throughput")
    lines.append(f"  swept      {thr['candidates']:,} candidates")
    lines.append(f"  rate       {_fmt_hs(thr.get('hs'))}"
                 + (f"  (roofline {roof:.2f})" if roof else ""))
    if thr.get("telemetry_hs") and thr.get("trace_hs"):
        lines.append(f"  telemetry  {_fmt_hs(thr['telemetry_hs'])}")
    ver = doc.get("verify")
    if ver:
        lines.append("")
        lines.append("host verify (every hit batch)")
        lines.append(f"  {ver['count']} batches  "
                     f"p50 {ver['p50_s'] * 1e3:.2f}ms  "
                     f"p95 {ver['p95_s'] * 1e3:.2f}ms  "
                     f"total {ver['total_s']:.3f}s")
    cov = doc.get("coverage")
    if cov:
        lines.append("")
        lines.append(f"coverage (audit verdict "
                     f"{cov['verdict'].upper()})")
        for j in cov.get("jobs") or ():
            frac = j.get("fraction")
            gap = j.get("gap_total")
            mark = {True: "match", False: "MISMATCH",
                    None: "n/a"}[j.get("digest_match")]
            lines.append(
                f"  {j['job'][:10]:10s} fraction "
                + (f"{frac:.4f}" if frac is not None else "   n/a")
                + f"  gaps {gap if gap is not None else '?'}"
                + f"  overlap {j.get('overlap', 0)}"
                + f"  digest {mark}"
                + (f"  hit dupes {j['hit_dupes']}"
                   if j.get("hit_dupes") else ""))
    busy = doc.get("busy") or {}
    if busy:
        lines.append("")
        lines.append("device busy fraction (sweep-span union)")
        for w in sorted(busy):
            lines.append(f"  {w:24s} {100 * busy[w]:>5.1f}%")
    cc = doc.get("compile_cache") or {}
    lines.append("")
    lines.append(
        "compile cache  hits "
        f"{cc.get('hits', 0)} / misses {cc.get('misses', 0)}"
        + (f"  (hit rate {100 * cc['hit_rate']:.0f}%)"
           if cc.get("hit_rate") is not None else ""))
    if doc.get("pipeline_depth") is not None:
        lines.append(f"pipeline depth {doc['pipeline_depth']:.0f}")
    health = doc.get("health")
    if health:
        lines.append("")
        lines.append("fleet health & alerts")
        fired = health.get("fired") or {}
        if fired:
            for rule in sorted(fired):
                lines.append(f"  fired {rule:24s} x{fired[rule]}")
        else:
            lines.append(f"  no alerts fired "
                         f"({health.get('alert_events', 0)} events)")
        unresolved = health.get("unresolved") or []
        if unresolved:
            lines.append("  UNRESOLVED at shutdown: "
                         + ", ".join(unresolved))
        workers = health.get("workers") or {}
        for w in sorted(workers):
            lines.append(f"  worker {w:20s} last transition -> "
                         f"{workers[w]}")
    memory = doc.get("memory")
    if memory:
        lines.append("")
        lines.append("device memory & program costs")
        for dev in sorted(memory.get("devices") or {}):
            rec = memory["devices"][dev]

            def _mb(k):
                v = rec.get(k)
                return f"{v / (1 << 20):,.0f}M" if v else "-"

            lines.append(f"  {dev:12s} in_use {_mb('in_use'):>9s}  "
                         f"peak {_mb('peak'):>9s}  "
                         f"limit {_mb('limit'):>9s}")
        for p in memory.get("programs") or ():
            lines.append(
                f"  program {p['engine']:12s} {p['attack']:12s} "
                f"peak {p['peak_bytes'] / (1 << 20):,.1f}M")
        div = memory.get("model_divergence") or {}
        for eng in sorted(div):
            flag = "  (>2x: MODEL DRIFT)" if div[eng] > 2 else ""
            lines.append(f"  roofline model divergence {eng}: "
                         f"{div[eng]:.2f}x{flag}")
    profiles = doc.get("profiles") or []
    if profiles:
        lines.append("")
        lines.append("kernel profile (captured windows)")
        for p in profiles:
            head = (f"  {p['worker']:20s} trigger "
                    f"{p.get('trigger') or '?':12s}")
            if p.get("error"):
                lines.append(head + f" FAILED: {p['error']}")
                continue
            fr = p.get("fractions") or {}
            head += (f" device {p.get('device_s') or 0.0:.4f}s  "
                     f"compute {100 * fr.get('compute', 0.0):.0f}% "
                     f"coll {100 * fr.get('collective', 0.0):.0f}% "
                     f"copy {100 * fr.get('copy', 0.0):.0f}%")
            d = p.get("divergence")
            if d:
                head += f"  divergence {d:.2f}x"
            lines.append(head)
            for op in (p.get("top_ops") or [])[:3]:
                lines.append(f"      {op.get('self_s', 0.0):>9.4f}s  "
                             f"{str(op.get('name'))[:56]}")
    fs = doc.get("fair_share") or []
    if len(fs) > 1:
        lines.append("")
        lines.append("fair share (lease counts vs weights)")
        lines.append(f"  {'JOB':6s} {'PRIO':>4s} {'LEASES':>7s} "
                     f"{'ACTUAL':>7s} {'WEIGHT':>7s}")
        for row in fs:
            lines.append(
                f"  {row['job'][:6]:6s} {row['priority']:>4d} "
                f"{row['leases']:>7d} "
                f"{100 * row['actual_share']:>6.1f}% "
                f"{100 * row['weight_share']:>6.1f}%")
    return "\n".join(lines)
