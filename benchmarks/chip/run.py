"""One run of one benchmark cell on the chip.

Usage, from the root of a checkout::

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration,
``configs/<config>.json`` beside this file, and a traffic mix,
``traffic/<mix>.json``.  The run

1. generates the deployment's road-network points from ``--seed`` and builds
   the engine the configuration names (set-up);
2. warms up every shape the mix uses (set-up), so that nothing compiles in
   the window;
3. drives the mix's entry point in a closed loop for ``--seconds``;
4. frees the engine and compares a seeded sample of the window's answers
   with the plain reference (``chipbench/reference.py``);
5. prints, as the last line of standard output, one JSON object with
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
   ``breakdown`` with ``--trace 1``), and as the last lines of standard
   error each compared number beside its limit.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is traced (the JAX profiler and the system's own
spans) and the metrics are the per-layer ones, each read by
``metrics/<metric>.py``.

A host where JAX finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(HERE))


class RunError(Exception):
    """A run that cannot produce a result (exit code ``code``)."""

    def __init__(self, message: str, code: int = 2):
        super().__init__(message)
        self.code = code


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(bench, cell, config, traffic)`` of one workload, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, mix


def metrics_for(bench: dict, key: str, cell: str) -> list[dict]:
    return [m for m in bench[key] if cell in m.get("workloads", [cell])]


def read_metric(name: str, ctx) -> float | None:
    """Run ``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def use_checkout_cache(jax) -> None:
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    and hand the same directory to the system under test."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make_engine(cfg: dict, facilities, users):
    """The configuration's engine, built with its ``engine_options`` as
    they stand (the engine rejects an option it does not know).  A
    ``ShardedEngine`` puts one shard on each chip the configuration asks
    for, so its ``shards`` has to equal ``chips``."""
    options = cfg["engine_options"]
    if cfg["engine"] == "RkNNEngine":
        from repro.core import RkNNEngine

        return RkNNEngine(facilities, users, **options)
    if cfg["engine"] == "ShardedEngine":
        if options.get("shards") != cfg["chips"]:
            raise RunError(
                f"ShardedEngine shards={options.get('shards')!r} differs from "
                f"chips={cfg['chips']!r}: one shard a chip"
            )
        from repro.shard import ShardedEngine

        return ShardedEngine(facilities, users, **options)
    raise RunError(f"unknown engine {cfg['engine']!r}")


def compile_counters() -> dict:
    from repro.obs import process_registry

    return {
        f"compile.count{{fn={labels['fn']}}}": c.value
        for labels, c in process_registry().find("compile.count")
    }


def scene_cache_counts(engine) -> tuple[int, int] | None:
    sc = engine.scene_cache
    return None if sc is None else (sc.hits, sc.misses)


def drive(engine, traffic, k: int, seconds: float, reservoir, log: dict) -> tuple[float, float]:
    """The measured window: the mix's entry point in a closed loop for
    ``seconds``.  Returns the window's ``(start, end)`` on the host clock;
    ``log`` gathers latencies, queries and each batch's triangle counts."""
    batches = traffic.batches()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if traffic.entry == "query_batch":
        while not log["latency_s"] or time.perf_counter() < t_end:
            qs = next(batches)
            t = time.perf_counter()
            res = engine.query_batch(qs, k)
            log["latency_s"].append(time.perf_counter() - t)
            log["queries"] += len(qs)
            log["tris"].append([s.n_tris for s in res.scenes])
            reservoir.offer((qs, res.masks, res.counts))
    elif traffic.entry == "stream":

        def until_end():
            yield next(batches)
            while time.perf_counter() < t_end:
                yield next(batches)

        for qs, masks in engine.stream(until_end(), k):
            log["queries"] += len(qs)
            log["latency_s"].append(None)
            reservoir.offer((qs, masks, None))
    else:
        raise RunError(f"unknown entry point {traffic.entry!r}")
    return t0, time.perf_counter()


def warm_up(engine, traffic, k: int) -> int:
    """Serves the mix's set-up batches; returns the most triangles a warm-up
    scene had (0 where the entry point does not return scenes)."""
    warm = traffic.warmup_batches()
    if traffic.entry == "stream":
        for _ in engine.stream(warm, k):
            pass
        return 0
    return max(max(s.n_tris for s in engine.query_batch(qs, k).scenes) for qs in warm)


def cache_files() -> str:
    """Files and bytes in the compilation cache directory."""
    files = [f for f in CACHE_DIR.rglob("*") if f.is_file()] if CACHE_DIR.is_dir() else []
    return f"{len(files)} files {sum(f.stat().st_size for f in files)} bytes"


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_chip: bool = True,
    overrides: dict | None = None,
    log_lines=None,
) -> dict:
    """One run of ``workload``; returns the result object.

    ``require_chip=False`` skips the look for a TPU (the tests drive the
    rest of a run on the CPU), and ``overrides`` replaces configuration
    values (the tests' small sizes).
    """
    say = log_lines if log_lines is not None else (lambda s: print(s, file=sys.stderr, flush=True))
    bench, cell, cfg, mix = load_cell(workload)
    cfg = {**cfg, **(overrides or {})}
    chips = int(cell["chips"])

    import jax

    if require_chip:
        use_checkout_cache(jax)
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise RunError(f"no TPU: JAX's devices are {devices[0].platform!r}", 3)
        if len(devices) < chips:
            raise RunError(f"{workload} needs {chips} chips, JAX sees {len(devices)}", 3)
    devices = jax.devices()
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise RunError(f"the system under test is missing: {e}", 4) from e

    from chipbench.check import LIMITS, Reservoir, compare
    from chipbench.compilewatch import CompileWatch
    from chipbench.data import RoadNetwork, facility_user_split
    from chipbench.reference import reference_ranks
    from chipbench.traffic import STREAM_SAMPLE, Traffic, seed_rng
    from chipbench.xplane import WINDOW_MARK, Trace, find_xplane, label_gaps
    from repro import obs

    k = int(cfg["k"])
    watch = CompileWatch(compile_counters)
    cache_at_start = cache_files()
    marks = [("devices", time.perf_counter())]
    net = RoadNetwork(int(cfg["points"]), seed)
    facilities, users = facility_user_split(net.points, int(cfg["facilities"]), seed)
    marks.append(("data", time.perf_counter()))
    engine = make_engine(cfg, facilities, users)
    traffic = Traffic(mix, cfg, seed, facilities, net)
    marks.append(("engine", time.perf_counter()))
    most_tris = warm_up(engine, traffic, k)
    marks.append(("warm-up", time.perf_counter()))
    phases = ", ".join(
        f"{name} {t - prev:.3f}" for (name, t), prev in zip(marks, [T_START] + [t for _, t in marks])
    )
    say(f"set-up: users={len(users)} facilities={len(facilities)} k={k} q={cfg['q']} "
        f"largest warm-up scene {most_tris} triangles, "
        f"pad bucket {getattr(engine, '_pad_bucket', None)}; "
        f"seconds: {phases}; compile cache {cache_at_start} -> {cache_files()}")

    reservoir = Reservoir(int(cfg["check_batches"]), seed_rng(seed, STREAM_SAMPLE))
    log = dict(latency_s=[], queries=0, tris=[])
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        tracer = obs.enable_tracing()
        tracer.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc.collect()
    compiles_before = watch.read()
    cache_before = scene_cache_counts(engine)
    with jax.profiler.TraceAnnotation(WINDOW_MARK):
        t0, t1 = drive(engine, traffic, k, seconds, reservoir, log)
    setup_s = t0 - T_START
    if trace:
        jax.profiler.stop_trace()
        obs.disable_tracing()
    compiled = CompileWatch.delta(compiles_before, watch.read())
    cache_after = scene_cache_counts(engine)
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    window_s = t1 - t0
    n_batches = len(log["latency_s"])
    lat = [t for t in log["latency_s"] if t is not None]
    spread = (
        " latency s p5/p50/p95 " + " ".join(
            f"{statistics.quantiles(lat, n=20)[i]:.4f}" for i in (0, 9, 18)
        ) if len(lat) > 1 else ""
    )
    say(f"window: {n_batches} batches, {log['queries']} queries in {window_s:.6f} s;"
        f"{spread} compiles in window {compiled}")

    spans = []
    if trace:
        spans = [
            (r["name"], r["t0"], r["t1"], r["depth"], r["attrs"])
            for r in obs.get_tracer().records()
            if r["t1"] > t0 and r["t0"] < t1
        ]
    del engine
    gc.collect()

    # ---- correctness: the sampled answers against the plain reference ----
    ux = jax.device_put(users[:, 0].astype("float32"), devices[0])
    uy = jax.device_put(users[:, 1].astype("float32"), devices[0])
    t_ref = time.perf_counter()
    checked = compare(reservoir.items, ux, uy, facilities, k, reference_ranks)
    say(f"reference: {checked['queries']} queries, {checked['users_checked']} users decided, "
        f"{checked['undecided']} undecided, {checked['counts_checked']} counts known, "
        f"in {time.perf_counter() - t_ref:.3f} s")
    del ux, uy
    compared = {
        name: {"value": checked[name], "limit": limit}
        for name, limit in LIMITS.items()
        if name != "wrong_counts" or checked["counts_checked"]
    }
    correct = checked["queries"] > 0 and all(
        c["value"] <= c["limit"] for c in compared.values()
    )

    result: dict = {
        "correct": bool(correct),
        "attempted": int(log["queries"]),
        "failed": int(checked["wrong_queries"]),
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if not trace:
        values = {
            "queries_per_s": log["queries"] / window_s,
            "setup_s": setup_s,
        }
        for m in metrics_for(bench, "end_to_end", workload):
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from chipbench.work import peaks_for

        tr = Trace.load(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window()
        offset = lo - t0 * 1e9  # perf_counter seconds -> trace ns
        ctx = types.SimpleNamespace(
            batches=n_batches,
            queries=log["queries"],
            window_s=window_s,
            spans=spans,
            compiles=compiled,
            scene_cache=(
                None if cache_before is None
                else (cache_after[0] - cache_before[0], cache_after[1] - cache_before[1])
            ),
            trace=tr,
            trace_window=(lo, hi),
            tris=log["tris"] if log["tris"] else None,
            n_users=len(users),
            peaks=peaks_for(devices[0].device_kind) if require_chip else None,
        )
        for m in metrics_for(bench, "per_layer", workload):
            v = read_metric(m["name"], ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tr.busy_s(lo, hi)
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = (hi - lo) / 1e9
        host_spans = [
            (name, s0 * 1e9 + offset, s1 * 1e9 + offset, depth)
            for name, s0, s1, depth, _ in spans
        ]
        gaps = sorted(label_gaps(tr.idle_gaps(lo, hi), host_spans), key=lambda g: -g[1])
        ops = sorted(tr.op_seconds(lo, hi).items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops[:10]],
            "idle_gaps": [[n, s] for n, s in gaps[:10]],
        }
    for name, c in compared.items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    result["checks"] = compared
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except RunError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return e.code
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
