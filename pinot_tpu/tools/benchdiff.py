"""Bench round differ: ``python -m pinot_tpu.tools.benchdiff OLD NEW``.

Compares two recorded bench rounds (``BENCH_r*.json``) and exits non-zero
when the new round regresses past a threshold — the CI face of the bench
artifacts the driver records every PR.

Input tolerance (both files): a round may be

- the bench's own stdout JSON (``{"metric": ..., "detail": {...}}``),
- the driver wrapper ``{"n", "cmd", "rc", "tail", "parsed"}`` where
  ``parsed`` is the full doc **or None** — then the known detail
  sections are brace-matched out of the truncated ``tail`` string, the
  same recovery bench.py's ``_load_micro_reference`` performs,
- partially populated (early rounds lack later phases): only metrics
  present in BOTH rounds are compared; everything else is reported as
  added/removed, never as a regression.

Compared metric families (direction-aware):

- per-suite query latencies (``ssb100m``/``taxi12m``/``subrtt`` entries'
  ``p50_ms`` — lower is better),
- micro kernel throughput (``micro.*.mrows_per_s`` — higher is better),
- concurrency throughput (``concurrency.n*.qps`` — higher is better),
- cluster-tier scaling (``cluster.servers.n*.qps`` /
  ``cluster.scaling_efficiency_2`` — higher is better — and
  ``cluster.result_cache.hit_p50_ms`` — lower is better), compared only
  when BOTH rounds carry a ``detail.cluster`` section,
- the phase waterfall (``observability.phase_p50_ms.*`` — lower is
  better; informational by default since queue/link phases are noisy,
  gated only under ``--gate-phases``),
- the per-kernel roofline (``roofline.kernels.*.gbps`` — higher is
  better — ISSUE 11's achieved-GB/s-vs-HBM-peak accounting), compared
  when both rounds carry a ``detail.roofline`` section (or the copy
  nested under ``observability``),
- the tiered-lifecycle phase (``tiering.per_tier.{hot,warm}.p50_ms`` +
  ``tiering.cold.hydrate_ms`` — lower is better — and
  ``tiering.peak_rss_delta_mb`` — lower is better — ISSUE 12), compared
  only when BOTH rounds carry a ``detail.tiering`` section,
- the overload-survival phase (``overload.knee_qps`` — higher is
  better — ``overload.p99_at_2x_knee_ms`` and
  ``overload.tenant_b.spike_p99_ms`` — lower is better — ISSUE 14),
  compared only when BOTH rounds carry a ``detail.overload`` section,
- the join phase (``join.join_p50_ms`` — lower is better — and the
  distributed stage-2 exchange trend keys ``join.stage2_qps`` — higher
  is better — ``join.exchange_bytes`` / ``join.spill_count`` —
  informational wire-volume and warm-tier-spill trackers, never gated:
  both move legitimately with partition count and buffer sizing —
  ISSUE 16), compared only when BOTH rounds carry the keys,
- the adaptive phase (``adaptive.*.converged_p50_ms`` — lower is
  better — the advisor's post-convergence latency on each deliberately
  mis-tuned scenario, plus ``adaptive.*.queries_to_converge`` —
  informational, never gated: it moves with min-samples/reprobe tuning —
  ISSUE 17), compared only when BOTH rounds carry a ``detail.adaptive``
  section,
- the frontdoor phase (``frontdoor.qps2_over_qps1`` — higher is better,
  the 2-broker scaling ratio — and ``frontdoor.stream_rss_delta_mb`` —
  lower is better, the streaming SELECT's broker RSS growth; ISSUE 18),
  compared only when BOTH rounds carry a ``detail.frontdoor`` section.
"""

from __future__ import annotations

import argparse
import json
import sys

# sections brace-matched out of a truncated driver-wrapper tail
_TAIL_SECTIONS = ("ssb100m", "taxi12m", "subrtt", "micro", "concurrency",
                  "observability", "blockskip", "narrow", "join", "faults",
                  "cluster", "breakdown", "roofline", "tiering", "overload",
                  "adaptive", "frontdoor")


def _brace_match(text: str, key: str):
    """json.loads the ``{...}`` object following ``"key":`` in ``text``,
    or None (absent / truncated mid-object). String-aware: braces inside
    JSON string values (a note containing '}' etc.) don't move the depth
    counter."""
    i = text.find(f'"{key}":')
    if i < 0:
        return None
    j = text.find("{", i)
    if j < 0:
        return None
    depth, k = 0, j
    in_string = escape = False
    while k < len(text):
        ch = text[k]
        if in_string:
            if escape:
                escape = False
            elif ch == "\\":
                escape = True
            elif ch == '"':
                in_string = False
        elif ch == '"':
            in_string = True
        elif ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                break
        k += 1
    try:
        return json.loads(text[j:k + 1])
    except ValueError:
        return None


def load_round(path: str) -> dict:
    """Round file → detail dict (best effort, never raises on partial
    rounds — an unreadable file IS an error).

    A round whose JSON parses to ``None``/empty (driver recorded a
    crashed run: ``parsed: null`` with no recoverable tail, or a bare
    ``null`` document) is SKIPPED with a warning instead of a traceback —
    every metric then reports as added/removed, never as a regression."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or not doc:
        print(f"benchdiff: warning: round {path!r} parsed to "
              f"{'empty' if doc == {} else type(doc).__name__}; "
              f"treating as an empty round", file=sys.stderr)
        return {}
    # driver wrapper?
    if "tail" in doc and "metric" not in doc:
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            doc = parsed
        else:
            tail = doc.get("tail") or ""
            detail = {}
            for sec in _TAIL_SECTIONS:
                got = _brace_match(tail, sec)
                if got is not None:
                    detail[sec] = got
            if not detail:
                print(f"benchdiff: warning: round {path!r} has no parsed "
                      f"doc and no recoverable tail sections",
                      file=sys.stderr)
            return detail
    if isinstance(doc.get("detail"), dict):
        return doc["detail"]
    return doc


def _num(v):
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def extract_metrics(detail: dict) -> dict:
    """detail → {metric_name: (value, direction)} where direction is
    "lower" (latency) or "higher" (throughput)."""
    out: dict = {}
    for suite in ("ssb100m", "taxi12m", "subrtt"):
        sec = detail.get(suite)
        if not isinstance(sec, dict):
            continue
        for qname, entry in sec.items():
            if isinstance(entry, dict):
                p50 = _num(entry.get("p50_ms"))
                if p50 is not None:
                    out[f"{suite}.{qname}.p50_ms"] = (p50, "lower")
    micro = detail.get("micro")
    if isinstance(micro, dict):
        for kname, entry in micro.items():
            if isinstance(entry, dict):
                rate = _num(entry.get("mrows_per_s"))
                if rate is not None:
                    out[f"micro.{kname}.mrows_per_s"] = (rate, "higher")
                # achieved bandwidth rides next to the row rate so the
                # Pallas scatter-tier micros (ISSUE 15) diff on their
                # GB/s-vs-HBM-peak axis too
                g = _num(entry.get("gbps"))
                if g is not None:
                    out[f"micro.{kname}.gbps"] = (g, "higher")
    conc = detail.get("concurrency")
    if isinstance(conc, dict):
        for lname, entry in conc.items():
            if isinstance(entry, dict):
                qps = _num(entry.get("qps"))
                if qps is not None:
                    out[f"concurrency.{lname}.qps"] = (qps, "higher")
    obs = detail.get("observability")
    if isinstance(obs, dict):
        phases = obs.get("phase_p50_ms")
        if isinstance(phases, dict):
            for pname, v in phases.items():
                v = _num(v)
                if v is not None:
                    out[f"phase.{pname}.p50_ms"] = (v, "lower")
    # per-kernel roofline (ISSUE 11): achieved GB/s per pipeline label —
    # higher is better; compared only when BOTH rounds carry the section
    # (falls back to the copy nested under observability for rounds that
    # predate the top-level promotion)
    roof = detail.get("roofline")
    if not isinstance(roof, dict):
        obs_sec = detail.get("observability")
        roof = obs_sec.get("roofline") if isinstance(obs_sec, dict) else None
    if isinstance(roof, dict):
        for kname, entry in (roof.get("kernels") or {}).items():
            if isinstance(entry, dict):
                g = _num(entry.get("gbps"))
                if g is not None:
                    out[f"roofline.{kname}.gbps"] = (g, "higher")
    clu = detail.get("cluster")
    if isinstance(clu, dict):
        servers = clu.get("servers")
        if isinstance(servers, dict):
            for lname, entry in servers.items():
                if isinstance(entry, dict):
                    qps = _num(entry.get("qps"))
                    if qps is not None:
                        out[f"cluster.{lname}.qps"] = (qps, "higher")
        eff = _num(clu.get("scaling_efficiency_2"))
        if eff is not None:
            out["cluster.scaling_efficiency_2"] = (eff, "higher")
        rc = clu.get("result_cache")
        if isinstance(rc, dict):
            p50 = _num(rc.get("hit_p50_ms"))
            if p50 is not None:
                out["cluster.result_cache.hit_p50_ms"] = (p50, "lower")
    # tiered lifecycle (ISSUE 12): per-tier p50s, hydration latency, and
    # the peak-RSS backstop — compared only when both rounds ran the phase
    tier = detail.get("tiering")
    if isinstance(tier, dict):
        per_tier = tier.get("per_tier")
        if isinstance(per_tier, dict):
            for tname in ("hot", "warm"):
                entry = per_tier.get(tname)
                if isinstance(entry, dict):
                    v = _num(entry.get("p50_ms"))
                    if v is not None:
                        out[f"tiering.{tname}.p50_ms"] = (v, "lower")
            cold = per_tier.get("cold")
            if isinstance(cold, dict):
                v = _num(cold.get("hydrate_ms"))
                if v is not None:
                    out["tiering.cold.hydrate_ms"] = (v, "lower")
        v = _num(tier.get("peak_rss_delta_mb"))
        if v is not None:
            out["tiering.peak_rss_delta_mb"] = (v, "lower")
    # overload-survival phase (ISSUE 14): the knee of the arrival-rate
    # ladder (higher is better), the p99 the cluster holds at 2x that
    # knee and the isolated tenant's p99 delta under the 10x spike
    # (lower is better), compared only when both rounds ran the phase;
    # shed/stale counts are load-dependent and stay informational
    ov = detail.get("overload")
    if isinstance(ov, dict):
        v = _num(ov.get("knee_qps"))
        if v is not None:
            out["overload.knee_qps"] = (v, "higher")
        v = _num(ov.get("p99_at_2x_knee_ms"))
        if v is not None:
            out["overload.p99_at_2x_knee_ms"] = (v, "lower")
        tb = ov.get("tenant_b")
        if isinstance(tb, dict):
            v = _num(tb.get("spike_p99_ms"))
            if v is not None:
                out["overload.tenant_b.spike_p99_ms"] = (v, "lower")
    # join phase (ISSUE 16): star-join p50 plus the distributed
    # stage-2 exchange trend line — QPS gates, wire volume and spill
    # count ride along informationally (see diff_rounds: info metrics
    # are reported but never regress)
    joi = detail.get("join")
    if isinstance(joi, dict):
        v = _num(joi.get("join_p50_ms"))
        if v is not None:
            out["join.join_p50_ms"] = (v, "lower")
        v = _num(joi.get("stage2_qps"))
        if v is not None:
            out["join.stage2_qps"] = (v, "higher")
        for k in ("exchange_bytes", "spill_count"):
            v = _num(joi.get(k))
            if v is not None:
                out[f"join.{k}"] = (v, "info")
    # adaptive phase (ISSUE 17): post-convergence p50 per mis-tuned
    # scenario gates (the advisor must keep rescuing the bad default);
    # queries-to-converge rides along informationally — it moves with
    # min_samples/reprobe tuning, both legitimate knobs
    ada = detail.get("adaptive")
    if isinstance(ada, dict):
        for sname, entry in ada.items():
            if isinstance(entry, dict):
                v = _num(entry.get("converged_p50_ms"))
                if v is not None:
                    out[f"adaptive.{sname}.converged_p50_ms"] = (v, "lower")
                v = _num(entry.get("queries_to_converge"))
                if v is not None:
                    out[f"adaptive.{sname}.queries_to_converge"] = (v, "info")
    # frontdoor phase (ISSUE 18): broker-tier scaling efficiency gates
    # (2-broker QPS over 1-broker, ceiling-normalized upstream in bench);
    # the streaming path's broker RSS delta is lower-is-better — a
    # regression means the front door started materializing again
    fd = detail.get("frontdoor")
    if isinstance(fd, dict):
        v = _num(fd.get("qps2_over_qps1"))
        if v is not None:
            out["frontdoor.qps2_over_qps1"] = (v, "higher")
        v = _num(fd.get("stream_rss_delta_mb"))
        if v is not None:
            out["frontdoor.stream_rss_delta_mb"] = (v, "lower")
    sub = detail.get("subrtt")
    if isinstance(sub, dict):
        # link_floor_ms is deliberately NOT compared: it is a property of
        # the box and its host<->device link, not the code (the served_p50 gate already
        # normalizes by it), same noise class as the ungated phases
        for k in ("served_p50_ms", "qps8"):
            v = _num(sub.get(k))
            if v is not None:
                direction = "higher" if k == "qps8" else "lower"
                out[f"subrtt.{k}"] = (v, direction)
    return out


def diff_rounds(old: dict, new: dict, threshold: float,
                gate_phases: bool = False) -> dict:
    """{regressions, improvements, unchanged, added, removed} over the
    shared metric set. A metric regresses when it moves past
    ``threshold`` (fraction) in its bad direction."""
    mo, mn = extract_metrics(old), extract_metrics(new)
    report = {"regressions": {}, "improvements": {}, "unchanged": {},
              "added": sorted(set(mn) - set(mo)),
              "removed": sorted(set(mo) - set(mn))}
    for name in sorted(set(mo) & set(mn)):
        vo, direction = mo[name]
        vn, _ = mn[name]
        if vo == 0:
            report["unchanged"][name] = {"old": vo, "new": vn}
            continue
        ratio = vn / vo
        entry = {"old": vo, "new": vn, "ratio": round(ratio, 3)}
        if direction == "info":
            # trend-only metric (exchange wire volume, spill count):
            # reported, never a regression or an improvement
            report["unchanged"][name] = entry
            continue
        worse = ratio > 1 + threshold if direction == "lower" \
            else ratio < 1 - threshold
        better = ratio < 1 - threshold if direction == "lower" \
            else ratio > 1 + threshold
        gated = gate_phases or not name.startswith("phase.")
        if worse and gated:
            report["regressions"][name] = entry
        elif better:
            report["improvements"][name] = entry
        else:
            report["unchanged"][name] = entry
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pinot_tpu.tools.benchdiff",
        description="compare two recorded bench rounds; non-zero exit on "
                    "regression past --threshold")
    ap.add_argument("old", help="reference round (BENCH_rNN.json)")
    ap.add_argument("new", help="candidate round")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="regression tolerance as a fraction (default 0.25)")
    ap.add_argument("--gate-phases", action="store_true",
                    help="also gate the per-phase waterfall (noisy: queue/"
                         "link phases swing with load; informational "
                         "otherwise)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)
    try:
        old = load_round(args.old)
        new = load_round(args.new)
    except (OSError, ValueError) as e:
        print(f"benchdiff: cannot read rounds: {e}", file=sys.stderr)
        return 2
    report = diff_rounds(old, new, args.threshold, args.gate_phases)
    if args.as_json:
        print(json.dumps(report, indent=2))
    else:
        for bucket in ("regressions", "improvements"):
            rows = report[bucket]
            if rows:
                print(f"{bucket} (threshold {args.threshold:.0%}):")
                for name, e in rows.items():
                    print(f"  {name}: {e['old']} -> {e['new']} "
                          f"(x{e['ratio']})")
        print(f"{len(report['unchanged'])} within threshold, "
              f"{len(report['added'])} added, "
              f"{len(report['removed'])} removed")
        if not report["regressions"]:
            print("no regressions")
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
