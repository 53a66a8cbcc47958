"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.05] [--only fig9] \
        [--json out.json] [--backend auto]

Prints ``name,us_per_call,derived`` CSV (one row per measured artefact).
``--scale 1.0`` reproduces the paper's dataset cardinalities (minutes to
hours on CPU); the default keeps CI fast while preserving every comparison.
``--json`` additionally writes the rows as machine-readable JSON
(``{"meta": {...}, "rows": [...]}``) so CI and future PRs can append
trajectory points (``BENCH_*.json``) without re-parsing CSV.
``--backend`` is forwarded to the benches that take one (currently the
planner's ``scenario_sweep``, which grades that backend against the fixed
set).
``--trend`` prints the committed ``benchmarks/results/BENCH_*.json``
trajectory (one CSV row per recorded measurement, tagged with its PR
number) instead of running anything — the cross-PR performance story in
one grep-able stream.
``--trend --gate`` additionally evaluates the trajectory against the
declared per-metric tolerances (``TREND_GATES``) and exits non-zero on
any regression — the bench trajectory as an enforced contract, not a
printout.  Each gate checks the *latest* committed point of every
matching artefact (history is context, not a verdict: a regression that
was already fixed stays visible in the trajectory without failing CI).
"""

from __future__ import annotations

import argparse
import glob
import inspect
import json
import os
import platform
import re
import sys
import time

from benchmarks import bench_rknn
from benchmarks.common import DEFAULT_SCALE
from repro.compile_cache import enable_compile_cache

BENCHES = [
    ("table2", bench_rknn.table2_indexing),
    ("fig7_8", bench_rknn.fig7_8_vary_k),
    ("fig9", bench_rknn.fig9_large_k),
    ("fig10", bench_rknn.fig10_datasize),
    ("fig11_12", bench_rknn.fig11_12_facility),
    ("fig13_14", bench_rknn.fig13_14_user),
    ("fig15", bench_rknn.fig15_breakdown),
    ("table3_fig16", bench_rknn.table3_fig16_occluders),
    ("fig17", bench_rknn.fig17_no_rt),
    ("backends", bench_rknn.backends_ablation),
    ("batch", bench_rknn.batch_throughput),
    ("engine", bench_rknn.engine_amortization),
    ("scenario_sweep", bench_rknn.scenario_sweep),
    ("update_throughput", bench_rknn.update_throughput),
    ("mono", bench_rknn.mono_queries),
    ("sharded_scaling", bench_rknn.sharded_scaling),
    ("obs_overhead", bench_rknn.obs_overhead),
    ("health_overhead", bench_rknn.health_overhead),
    ("cold_start", bench_rknn.cold_start),
]

#: The declared cross-PR tolerances (``--trend --gate``).  ``row`` is a
#: substring filter on artefact names; ``key`` extracts a ``key=value``
#: KPI from the derived string (suffixes like ``x``/``ms`` stripped) and
#: is checked against ``min``/``max``; ``flag`` requires a literal token
#: in the derived string.  ``fallback_flag`` passes a row whose KPI is
#: absent (older artefact shapes).  Values mirror the per-bench CI
#: assertions so the trajectory gate and the fresh-run gates agree.
TREND_GATES = [
    dict(id="obs-overhead", row="obs_overhead", key="ratio", max=1.03),
    dict(id="health-overhead", row="health_overhead", key="ratio", max=1.05),
    dict(id="planner-drift", row="planner_drift", key="worst_abs_median", max=1.5),
    dict(
        id="scenario-aggregate",
        row="scenario_aggregate",
        key="agg_ratio",
        max=1.25,
        fallback_flag="beats_all=True",
    ),
    dict(id="mvcc-concurrent", row="update_concurrent", flag="within2x=True"),
    dict(id="mvcc-stale", row="update_concurrent", flag="stale_mix=0"),
    dict(id="shard-scaling-monotone", row="_scaling", flag="monotone=True"),
    dict(id="shard-scaling-speedup", row="_scaling", key="s1/s4", min=1.5),
    dict(id="refit-drift-win", row="update_drift", key="speedup", min=1.0),
    dict(id="cold-start", row="cold_start", key="speedup", min=3.0),
    dict(id="cold-start-identical", row="cold_start", flag="identical=True"),
]

_NUM_RE = re.compile(r"-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?")


def _kpi(derived: str, key: str) -> float | None:
    """Extract ``key=<number>`` from a derived string (unit suffixes like
    ``x`` / ``ms`` ignored); ``None`` when the key is absent."""
    for tok in derived.replace(";", " ").split():
        k, eq, v = tok.partition("=")
        if eq and k == key:
            m = _NUM_RE.match(v)
            return float(m.group(0)) if m else None
    return None


def _load_results(paths: list[str] | None = None) -> list[tuple[int, list[dict]]]:
    if paths is None:
        results = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "results"
        )
        paths = glob.glob(os.path.join(results, "BENCH_*.json"))
    out = []
    for path in sorted(
        paths, key=lambda p: int(re.search(r"BENCH_(\d+)", p).group(1))
    ):
        with open(path) as f:
            payload = json.load(f)
        pr = int(re.search(r"BENCH_(\d+)", path).group(1))
        out.append((pr, payload.get("rows", [])))
    return out


def evaluate_trend(paths: list[str] | None = None) -> dict:
    """Grade the committed trajectory against :data:`TREND_GATES`.

    Returns ``{"lines": [...], "failures": [...]}`` — one line per
    (gate, artefact) with the full cross-PR KPI trajectory and the
    verdict on the latest point; failures collect the lines that fail.
    Usable directly from tests (pass explicit paths for fixtures).
    """
    data = _load_results(paths)
    lines: list[str] = []
    failures: list[str] = []
    for gate in TREND_GATES:
        series: dict[str, list[tuple[int, str]]] = {}
        for pr, rows in data:
            for r in rows:
                if gate["row"] in r.get("name", ""):
                    series.setdefault(r["name"], []).append(
                        (pr, str(r.get("derived", "")))
                    )
        if not series:
            lines.append(f"SKIP {gate['id']}: no committed data")
            continue
        for name, pts in sorted(series.items()):
            pts.sort(key=lambda t: t[0])
            latest_pr, derived = pts[-1]
            verdict, shown = _grade(gate, derived)
            traj = " ".join(
                f"pr{pr}:{_kpi(d, gate['key']) if 'key' in gate else ('ok' if gate['flag'] in d else 'FAIL')}"
                for pr, d in pts
            )
            line = (
                f"{'PASS' if verdict else 'FAIL'} {gate['id']}: {name} "
                f"latest=pr{latest_pr} {shown} | {traj}"
            )
            lines.append(line)
            if not verdict:
                failures.append(line)
    return {"lines": lines, "failures": failures}


def _grade(gate: dict, derived: str) -> tuple[bool, str]:
    """Verdict for one artefact's latest derived string under one gate."""
    if "key" in gate:
        v = _kpi(derived, gate["key"])
        if v is None:
            fb = gate.get("fallback_flag")
            if fb is not None:
                ok = fb in derived
                return ok, f"{fb} {'present' if ok else 'ABSENT'}"
            return False, f"{gate['key']} missing"
        lo, hi = gate.get("min"), gate.get("max")
        if hi is not None and v > hi:
            return False, f"{gate['key']}={v:g} > max {hi:g}"
        if lo is not None and v < lo:
            return False, f"{gate['key']}={v:g} < min {lo:g}"
        bound = f"<= {hi:g}" if hi is not None else f">= {lo:g}"
        return True, f"{gate['key']}={v:g} ({bound})"
    flag = gate["flag"]
    ok = flag in derived
    return ok, f"{flag} {'present' if ok else 'ABSENT'}"


def print_trend() -> None:
    """The committed BENCH_*.json trajectory as one CSV stream.

    Each committed file is one PR's acceptance measurement; printing them
    in PR order makes per-artefact trajectories (`grep sharded_`,
    `grep scenario_aggregate`) readable across the repo's history."""
    results = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    files = sorted(
        glob.glob(os.path.join(results, "BENCH_*.json")),
        key=lambda p: int(re.search(r"BENCH_(\d+)", p).group(1)),
    )
    if not files:
        print(f"# no committed BENCH_*.json under {results}", file=sys.stderr)
        return
    print("pr,bench,name,us_per_call,derived")
    for path in files:
        with open(path) as f:
            payload = json.load(f)
        pr = int(re.search(r"BENCH_(\d+)", path).group(1))
        meta = payload.get("meta", {})
        print(
            f"# BENCH_{pr}: scale={meta.get('scale')} "
            f"wall={meta.get('wall_s')}s only={meta.get('only')}",
            file=sys.stderr,
        )
        for r in payload.get("rows", []):
            derived = str(r.get("derived", "")).replace(",", ";")
            print(
                f"{pr},{r.get('bench', '?')},{r['name']},"
                f"{float(r['us_per_call']):.1f},{derived}"
            )
        for e in payload.get("errors", []):
            print(f"{pr},{e.get('bench', '?')}_ERROR,,0,{e.get('error')}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=DEFAULT_SCALE)
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated substring filters on bench name (any match runs)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT",
        help="record engine spans for the whole run and write a Chrome "
        "trace_event JSON (open in chrome://tracing or Perfetto)",
    )
    ap.add_argument(
        "--json",
        default=None,
        metavar="OUT",
        help="also write rows as machine-readable JSON to this path",
    )
    ap.add_argument(
        "--backend",
        default=None,
        help="backend name forwarded to benches that accept one",
    )
    ap.add_argument(
        "--concurrent",
        action="store_true",
        help="forwarded to benches that accept it (update_throughput: "
        "measure MVCC serving latency under a concurrent update stream)",
    )
    ap.add_argument(
        "--trend",
        action="store_true",
        help="print the committed benchmarks/results/BENCH_*.json "
        "trajectory as CSV and exit (runs nothing)",
    )
    ap.add_argument(
        "--gate",
        action="store_true",
        help="with --trend: grade the trajectory against the declared "
        "TREND_GATES tolerances and exit non-zero on any regression",
    )
    args = ap.parse_args()
    if args.trend:
        print_trend()
        if args.gate:
            report = evaluate_trend()
            print("\n# trend gate:", file=sys.stderr)
            for line in report["lines"]:
                print(f"# {line}", file=sys.stderr)
            if report["failures"]:
                print(
                    f"# trend gate: {len(report['failures'])} regression(s)",
                    file=sys.stderr,
                )
                sys.exit(1)
            print("# trend gate: green", file=sys.stderr)
        return

    if args.trace:
        from repro.obs import enable_tracing

        enable_tracing()

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    all_rows: list[dict] = []
    errors: list[dict] = []
    only = [s for s in (args.only or "").split(",") if s]
    for name, fn in BENCHES:
        if only and not any(s in name for s in only):
            continue
        kw = {"scale": args.scale}
        if args.backend and "backend" in inspect.signature(fn).parameters:
            kw["backend"] = args.backend
        if args.concurrent and "concurrent" in inspect.signature(fn).parameters:
            kw["concurrent"] = True
        try:
            rows = fn(**kw)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"{name}_ERROR,0,{type(e).__name__}: {e}", file=sys.stdout)
            errors.append(dict(bench=name, error=f"{type(e).__name__}: {e}"))
            continue
        for r in rows:
            derived = str(r.get("derived", "")).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.1f},{derived}", flush=True)
            all_rows.append(
                dict(
                    bench=name,
                    name=r["name"],
                    us_per_call=float(r["us_per_call"]),
                    derived=str(r.get("derived", "")),
                )
            )
    wall = time.perf_counter() - t0
    if args.trace:
        from repro.obs import disable_tracing, write_chrome_trace

        disable_tracing()
        obj = write_chrome_trace(args.trace)
        n = sum(1 for e in obj["traceEvents"] if e.get("ph") == "X")
        print(f"# wrote {n} spans to {args.trace}", file=sys.stderr)
    if args.json:
        payload = dict(
            meta=dict(
                scale=args.scale,
                only=args.only,
                backend=args.backend,
                wall_s=round(wall, 3),
                python=platform.python_version(),
                platform=platform.platform(),
            ),
            rows=all_rows,
            errors=errors,
        )
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)
    print(f"# total wall: {wall:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
