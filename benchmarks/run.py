"""Benchmark harness — one function per paper table/figure.

Prints each figure's detailed CSV block, then a summary line per table in
``name,us_per_call,derived`` form (us_per_call = wall time of the harness
function; derived = the table's headline number).

  PYTHONPATH=src python -m benchmarks.run [--obs] [--only a,b] \
      [--summary-json BENCH_summary.json]

Sections are failure-isolated: an exception in one sweep is recorded as
that section's status and the run continues, so the machine-readable
artifacts are never empty. ``BENCH_summary.json`` (rewritten after
*every* section, so even a hard crash leaves the completed prefix)
carries per-section ``status``/``derived``/``error``/``seconds``; any
section that should have produced a ``BENCH_*.json`` but died before
its sweep finished gets a stub file with the failure recorded.

``--obs`` additionally runs an instrumented observability pass
(``repro.obs`` — in-graph metrics fabric + span tracer) and attaches
its output as a ``metrics`` section to every ``BENCH_*.json`` written
by the run: delivery-latency histogram + bucketed p50/p95/p99, HWMs,
event counters, and the host-span rollup with the drain-overlap ratio.
List-shaped BENCH files are wrapped to ``{"rows": [...], "metrics":
{...}}`` in that mode; without ``--obs`` their schema is unchanged.

Every section runs in this one process, so on a chip host nothing else
competes for the chip. The cross-pod collective sweep needs 32 virtual
CPU devices, which a chip host cannot give, so it is not a section: run
it on its own as ``python -m benchmarks.bench_crosspod``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.compile_cache import use_compile_cache


def _timed(name, fn):
    t0 = time.time()
    derived = fn()
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived}")
    return derived


def _dump_json(path, rows):
    """Machine-readable perf trajectory (BENCH_*.json next to the run)."""
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    print(f"# wrote {path}")


def fig8():
    from benchmarks import fig8_scalability as m
    rs = m.rows()
    m.main()
    geo19 = [r for r in rs if r["n"] == 19 and r["net"] == "geo"][0]
    return f"picsou_vs_ata_geo_n19={geo19['ratio']:.1f}x(paper 24x)"


def fig9():
    from benchmarks import fig9_failures_stakes as m
    m.main()
    rows = m.stake_scenarios()
    unfair = [r for r in rows if r["scenario"] == "unfair"][0]
    return f"unfair_drop={1 - unfair['vs_equal']:.0%}(paper 87%)"


def fig10():
    from benchmarks import fig10_heterogeneous as m
    rs = m.rows()
    m.main()
    worst = max(r["overhead_frac"] for r in rs)
    return f"worst_overhead={worst:.1%}(paper <15%)"


def thm1():
    from benchmarks import bench_retransmit as m
    m.main()
    curve = m.delivery_probability_curve(max_retries=8)
    return f"p_delivery_8_retries={curve[-1]['p_delivery']:.4f}(paper 99.9%)"


def kernels():
    import jax

    from benchmarks import bench_kernels as m
    m.main()
    dev = jax.devices()[0]
    return f"{m.mode()}_on_{dev.platform}({dev.device_kind})"


def windowed():
    from benchmarks import bench_windowed as m
    rs = m.main()
    _dump_json("BENCH_windowed.json", rs)
    big = [r for r in rs if r.get("path") == "windowed"][-1]
    dense_big = [r for r in rs if r.get("path") == "dense"
                 and r["n_msgs"] == big["n_msgs"]][0]
    ratio = dense_big["state_bytes"] / max(big["state_bytes"], 1)
    return (f"state@{big['n_msgs']}={big['state_bytes']}B"
            f"(const,W={big['window_slots']}),dense/windowed_state="
            f"{ratio:.1f}x")


def pipeline():
    from benchmarks import bench_pipeline as m
    rs = m.main(json_path="BENCH_pipeline.json")
    singles = [r for r in rs if r["batch"] == 1]
    big_m = max(r["n_msgs"] for r in singles)
    best = max((r for r in singles if r["n_msgs"] == big_m),
               key=lambda r: r["k"])
    sync = [r for r in singles
            if r["n_msgs"] == big_m and r["k"] == 1][0]
    return (f"K={best['k']}@{big_m}="
            f"{best.get('speedup_vs_sync', 1.0):.2f}x_warm,"
            f"dispatches{sync['dispatches']}->{best['dispatches']},"
            f"syncs{sync['host_syncs']}->{best['host_syncs']}")


def topology():
    from benchmarks import bench_topology as m
    rs = m.main(json_path="BENCH_topology.json")
    fan = [r for r in rs if r["section"] == "fanout"
           and r["scenario"] == "none"]
    big = max(fan, key=lambda r: (r["links"], r["n_msgs"]))
    chain = [r for r in rs if r["section"] == "chain"]
    lag = chain[-1]["pipeline_lag_rounds"] if chain else "n/a"
    return (f"{big['links']}links@{big['n_msgs']}msgs_warm="
            f"{big['warm_s']:.2f}s,chain_lag={lag}rounds")


def stream():
    from benchmarks import bench_stream as m
    rs = m.main(json_path="BENCH_stream.json")
    cal = [r for r in rs if r.get("utilization")]
    if cal:
        best = max(cal, key=lambda r: r["utilization"])
        return (f"sustained={best['sustained_frac']:.0%}_of_capacity"
                f"@u={best['utilization']:.2f},fleet={best['fleet']},"
                f"p99={best['p99']}")
    big = max(rs, key=lambda r: r["horizon"])
    return f"sustained={big['sustained_frac']:.0%}_of_capacity"


def replay():
    from benchmarks import bench_replay as m
    rs = m.main(json_path="BENCH_replay.json")
    fk = [r for r in rs if r["section"] == "forks"]
    big = max(fk, key=lambda r: (r["forks"], r["n_msgs"]))
    rec = [r for r in rs if r["section"] == "record"][-1]
    return (f"{big['forks']}forks@{big['n_msgs']}msgs_warm="
            f"{big['warm_s']:.2f}s({big['warm_s_per_fork']:.3f}s/fork,"
            f"{big['chunk_traces_warm']}recompiles),record_overhead="
            f"{rec['record_overhead']:.0%}")


def adversary():
    from benchmarks import bench_adversary as m
    rs = m.main(json_path="BENCH_adversary.json")
    pal = [r for r in rs if r["section"] == "palette"
           and r["kind"] != "honest"]
    big_m = max(r["n_msgs"] for r in pal)
    extra = sum(r["extra_traces"] for r in rs)
    worst = max((r for r in pal if r["n_msgs"] == big_m),
                key=lambda r: r["resends"])
    rec = [r for r in rs if r["section"] == "reconfig"][-1]
    return (f"palette{len({r['kind'] for r in pal})}@{big_m},"
            f"worst={worst['kind']}({worst['resends']}resends),"
            f"reconfig_warm={rec['warm_s']:.2f}s,extra_traces={extra}")


# section name -> (harness fn, BENCH json the sweep is expected to emit)
TABLES = (("fig8_scalability", fig8, None),
          ("fig9_failures_stakes", fig9, None),
          ("fig10_heterogeneous", fig10, None),
          ("thm1_retransmit", thm1, None),
          ("windowed_sim", windowed, "BENCH_windowed.json"),
          ("pipeline", pipeline, "BENCH_pipeline.json"),
          ("topology_apps", topology, "BENCH_topology.json"),
          ("replay_whatif", replay, "BENCH_replay.json"),
          ("stream", stream, "BENCH_stream.json"),
          ("adversary", adversary, "BENCH_adversary.json"),
          ("kernels", kernels, None))

# regression gate knobs for --compare: a section regresses when its wall
# time grows by more than REGRESSION_FRAC over the prior summary AND the
# absolute growth clears REGRESSION_FLOOR_S (sub-second jitter on tiny
# sections is not a regression)
REGRESSION_FRAC = 0.15
REGRESSION_FLOOR_S = 1.0


def compare_summaries(prev: dict, cur: dict,
                      frac: float = REGRESSION_FRAC,
                      floor_s: float = REGRESSION_FLOOR_S):
    """Diff two BENCH_summary.json documents section-by-section.

    Returns ``(lines, regressions)`` — a printable report over every
    section present in both summaries (wall-time delta + derived-metric
    change), and the subset of lines that constitute wall-time
    regressions (> ``frac`` slower AND > ``floor_s`` absolute growth,
    ok-status sections only). New/removed sections are reported but are
    never regressions.
    """
    pv = {s["name"]: s for s in prev.get("sections", ())}
    cv = {s["name"]: s for s in cur.get("sections", ())}
    lines, regressions = [], []
    for name, c in cv.items():
        p = pv.get(name)
        if p is None:
            lines.append(f"  {name}: new section "
                         f"({c.get('seconds', 0):.2f}s)")
            continue
        ps, cs = float(p.get("seconds", 0)), float(c.get("seconds", 0))
        delta = cs - ps
        ratio = (cs / ps - 1.0) if ps > 0 else 0.0
        line = f"  {name}: {ps:.2f}s -> {cs:.2f}s ({ratio:+.0%})"
        if p.get("derived") != c.get("derived"):
            line += f"; derived {p.get('derived')} -> {c.get('derived')}"
        if (p.get("status"), c.get("status")) != ("ok", "ok"):
            line += (f"; status {p.get('status')} -> {c.get('status')}")
        elif ratio > frac and delta > floor_s:
            line += "  ** REGRESSION"
            regressions.append(line)
        lines.append(line)
    for name in pv.keys() - cv.keys():
        lines.append(f"  {name}: section missing from current run")
    return lines, regressions


def obs_metrics_section(n_msgs: int = 4096, k: int = 8) -> dict:
    """One instrumented observability run (``repro.obs``) as a JSON
    section: device latency histogram + percentiles and the host span
    rollup, so every BENCH artifact carries measured distributions next
    to its headline ratios."""
    from repro.core.simulator import build_spec
    from repro.core.types import RSMConfig, SimConfig
    from repro.obs.report import run_reported
    sim = SimConfig(n_msgs=n_msgs, steps=n_msgs // 4 + 96, window=4,
                    phi=6, window_slots="auto", chunk_steps=32,
                    superchunk=k, collect_metrics=True)
    spec = build_spec(RSMConfig.bft(1), RSMConfig.bft(1), sim)
    _, report = run_reported(spec)
    problems = report.validate()
    span = report.spans
    return {
        "shape": {"n_msgs": n_msgs, "superchunk": k,
                  "window_slots": report.meta["window_slots"]},
        "obs": report.obs["link"].to_dict(),
        "drain_overlap_ratio": span["drain_overlap_ratio"],
        "no_drains": span.get("no_drains", False),
        "span_totals_ms": _span_totals_ms(span),
        "dispatches": report.meta["chunk_dispatches"],
        "validated": not problems,
        "problems": problems,
    }


def _span_totals_ms(span_dict: dict) -> dict:
    totals: dict = {}
    for s in span_dict.get("spans", ()):
        totals[s["name"]] = totals.get(s["name"], 0.0) + s["dur_ns"] / 1e6
    return {k: round(v, 3) for k, v in sorted(totals.items())}


def _attach_metrics(path: str, metrics: dict) -> None:
    """Add a ``metrics`` section to one BENCH json (wrapping row lists)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = []
    if isinstance(doc, list):
        doc = {"rows": doc}
    doc["metrics"] = metrics
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=float)
    print(f"# attached metrics section to {path}")


def _write_stub(path: str, section: str, error: str) -> None:
    """Failed sweeps still leave a (status-carrying) BENCH artifact."""
    _dump_json(path, {"rows": [], "section": section,
                      "status": "failed", "error": error})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.run")
    ap.add_argument("--obs", action="store_true",
                    help="run an instrumented repro.obs pass and attach "
                         "a metrics section to every BENCH_*.json")
    ap.add_argument("--only", default=None,
                    help="comma-separated section names to run")
    ap.add_argument("--summary-json", default="BENCH_summary.json")
    ap.add_argument("--compare", default=None, metavar="PREV_summary.json",
                    help="after the run, diff the fresh summary against "
                         "this prior BENCH_summary.json and exit nonzero "
                         "on a >15%% warm wall-time regression in any "
                         "section (small absolute deltas are ignored)")
    args = ap.parse_args(argv)
    use_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    tables = [t for t in TABLES if only is None or t[0] in only]
    if only:
        unknown = only - {t[0] for t in TABLES}
        if unknown:
            ap.error(f"unknown sections: {sorted(unknown)}; "
                     f"have {[t[0] for t in TABLES]}")

    print("== PICSOU / C3B benchmark suite ==")
    summary = []

    def flush_summary():
        _dump_json(args.summary_json,
                   {"status": ("ok" if all(s["status"] == "ok"
                                           for s in summary) else "partial"),
                    "sections": summary})

    for name, fn, bench_json in tables:
        print(f"\n### {name}")
        t0 = time.time()
        entry = {"name": name, "status": "ok", "error": None}
        try:
            entry["derived"] = fn()
        except Exception as e:  # noqa: BLE001
            entry.update(status="failed", derived=f"FAILED:{type(e).__name__}",
                         error=f"{type(e).__name__}: {e}")
            if bench_json and not os.path.exists(bench_json):
                _write_stub(bench_json, name, entry["error"])
        entry["seconds"] = round(time.time() - t0, 3)
        summary.append(entry)
        flush_summary()   # crash-safe: completed prefix always on disk

    if args.obs:
        print("\n### obs (instrumented metrics pass)")
        t0 = time.time()
        try:
            metrics = obs_metrics_section()
        except Exception as e:  # noqa: BLE001
            metrics = {"validated": False,
                       "problems": [f"{type(e).__name__}: {e}"]}
        for _, _, bench_json in tables:
            if bench_json and os.path.exists(bench_json):
                _attach_metrics(bench_json, metrics)
        summary.append({"name": "obs", "error": None,
                        "seconds": round(time.time() - t0, 3),
                        "status": "ok" if metrics.get("validated")
                        else "failed",
                        "derived": f"drain_overlap="
                        f"{metrics.get('drain_overlap_ratio', 0):.3f}"})
        flush_summary()

    print("\n== summary (name,us_per_call,derived) ==")
    for s in summary:
        print(f"{s['name']},{s['seconds'] * 1e6:.0f},{s['derived']}")

    rc = 0 if all(s["status"] == "ok" for s in summary) else 1
    if args.compare:
        print(f"\n== compare vs {args.compare} ==")
        try:
            with open(args.compare) as f:
                prev = json.load(f)
        except (OSError, ValueError) as e:
            print(f"  (no usable baseline: {e})")
            return rc
        lines, regressions = compare_summaries(
            prev, {"sections": summary})
        for line in lines:
            print(line)
        if regressions:
            print(f"\n{len(regressions)} wall-time regression(s) "
                  f"(>{REGRESSION_FRAC:.0%} and "
                  f">{REGRESSION_FLOOR_S:.0f}s slower)")
            rc = rc or 2
        else:
            print("no wall-time regressions")
    return rc


if __name__ == "__main__":
    sys.exit(main())
