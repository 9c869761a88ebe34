#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload paper4.explore_cold --seed 7 \\
      --seconds 30 --trace 0

The cell, its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``) and its per-layer
metrics (``bench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``. A run:

1. builds the capture from ``--seed``: the rank DBs, one process per
   rank, then the trace store through ``VariabilityPipeline.generate``
   on the pipeline's ``process`` backend. JAX is not touched before this;
2. refuses to go on (exit 2, no result line) unless JAX's devices are
   TPUs, as many as the cell asks for;
3. serves the store with ``VariabilityPipeline.serve`` on the jax
   backend over the cell's chips, with the persistent compile cache on,
   and warms it up on the mix's own traffic from a seed stream apart
   from the window's; a cold mix then starts from empty caches. All of
   that, from the start of the process, is ``setup_s``;
4. measures ``--seconds`` of traffic from a load generator in a process
   of its own (``bench/loadgen.py``); with ``--trace 1`` under the
   profiler, reading the per-layer metrics instead of the end-to-end
   ones;
5. once the window is closed and the service stopped, compares a
   sample of the window's answers, drawn from the seed, with the plain
   reference (``bench/reference.py``), and prints each number compared
   beside its limit on standard error and in the result line.

The last line of standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

if __name__ == "__main__":
    # the cell runners import this file as ``run``: one module, one clock
    sys.modules["run"] = sys.modules["__main__"]

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import instrument  # noqa: E402
import reference  # noqa: E402
import workload  # noqa: E402

WORK = os.path.join(ROOT, ".bench_work")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX shows no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- the benchmark's files ------------------------------------------------------

def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: Dict, name: str) -> Dict:
    """Everything one cell names: the workload entry, its configuration
    file, its traffic mix, and the metric entries it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    mix = workload.load_traffic(cell["traffic"])
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in moved)]
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": e2e, "per_layer": layer}


def reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --- the chip -------------------------------------------------------------------

def chip_visible() -> bool:
    """A cheap look, before the set-up: JAX may use a TPU here unless
    ``JAX_PLATFORMS`` leaves it out. ``chip_devices`` makes the real
    check once the capture is built."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        return False
    return True


def chip_devices(n_chips: int, require_tpu: bool) -> List:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips, JAX shows "
                     f"{len(devs)}")
    return devs[:n_chips]


class Compiles:
    """Backend compiles (persistent-cache loads included), timed, and
    the persistent-cache hits among them."""

    def __init__(self) -> None:
        self.at: List[float] = []
        self.secs: List[float] = []
        self.hits_at: List[float] = []

    def install(self) -> "Compiles":
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.at.append(time.monotonic())
                self.secs.append(float(secs))

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits_at.append(time.monotonic())

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.at if t0 <= t <= t1)

    def summary(self, t0: float, t1: float) -> str:
        hits = sum(1 for t in self.hits_at if t0 <= t <= t1)
        secs = sum(s for t, s in zip(self.at, self.secs) if t0 <= t <= t1)
        return (f"{self.between(t0, t1)} compiles ({hits} from the "
                f"persistent cache, {secs:.3f} s)")


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# --- load generation --------------------------------------------------------------

def drive(work: str, tag: str, spec: Dict, annotate: Optional[str] = None
          ) -> Dict:
    """Run the load generator once, in its own process; returns what it
    recorded."""
    spec_path = os.path.join(work, f"load_{tag}.json")
    out_path = os.path.join(work, f"load_{tag}.out.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    cmd = [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path,
           out_path]
    proc = subprocess.Popen(cmd)
    try:
        if annotate:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation(annotate):
                rc = proc.wait()
        else:
            rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise RuntimeError(f"load generator ({tag}) exited with {rc}")
    with open(out_path) as f:
        return json.load(f)


# --- one explore cell -------------------------------------------------------------

def run_explore(ctx: Dict, seed: int, seconds: float, trace: bool,
                work: str, require_tpu: bool = True) -> Dict:
    from store import build_store, make_dataset, write_rank_dbs

    config, mix = ctx["config"], ctx["mix"]
    gen = config["generation"]
    ds = make_dataset(config, seed)
    paths = write_rank_dbs(ds.traces, os.path.join(work, "dbs"))
    store_dir = os.path.join(work, "store")
    rep = build_store(paths, store_dir, config)
    log(f"store: {rep.joined_rows} joined rows in {rep.n_shards} shards "
        f"({rep.rows_per_table['KERNEL']} kernels, "
        f"{rep.rows_per_table['MEMCPY']} memcpys), ready at "
        f"{time.monotonic() - T_PROCESS:.3f} s")

    devices = chip_devices(int(ctx["cell"]["chips"]), require_tpu)
    from repro.compile_cache import enable_compile_cache
    from repro.core import PipelineConfig, TraceStore, VariabilityPipeline
    log(f"compile cache: {enable_compile_cache()}; chips ready at "
        f"{time.monotonic() - T_PROCESS:.3f} s")
    compiles = Compiles().install()

    t_first = min(int(tr.kernels.start.min()) for tr in ds.traces)
    t_last = max(int(tr.kernels.end.max()) for tr in ds.traces)
    plan = reference.Plan(t_first, t_last, int(gen["interval_ns"]))
    base = {"mix": ctx["cell"]["traffic"], "seed": int(seed),
            "t_start": plan.t_start,
            "t_end": plan.t_start + plan.n * plan.interval,
            "n_ranks": int(config["n_ranks"])}

    pipe = VariabilityPipeline(PipelineConfig(
        n_ranks=int(config["n_ranks"]), backend="jax",
        devices=list(devices)))
    svc = pipe.serve(store_dir, port=0)
    recorder = None
    try:
        base["port"] = svc.cfg.port
        if mix.get("pool"):
            pool = workload.hot_pool(mix, seed, base["t_start"],
                                     base["t_end"], base["n_ranks"])
            warm = drive(work, "warm", dict(base, queries=pool))
        else:
            warm = drive(work, "warm", dict(
                base, stream=workload.WARMUP,
                per_session=int(mix["warmup_per_session"])))
        bad = [r for r in warm["records"] if r["status"] != 200]
        if bad:
            raise RuntimeError(f"warm-up query failed: {bad[0]}")
        if mix.get("cold"):
            store = TraceStore(store_dir)
            store.clear_summaries()
            store.clear_partials()
        setup_s = time.monotonic() - T_PROCESS
        log(f"set-up: {setup_s:.3f} s, {len(warm['records'])} warm-up "
            f"queries, {compiles.summary(0.0, time.monotonic())}")

        trace_dir = os.path.join(work, "trace")
        if trace:
            recorder = instrument.start(trace_dir)
        try:
            win = drive(work, "window", dict(base, stream=workload.WINDOW,
                                             seconds=float(seconds)),
                        annotate="bench.window" if trace else None)
        finally:
            if trace:
                instrument.stop(recorder)
        peak = memory_peak(devices)
    finally:
        svc.stop()

    records = win["records"]
    done = [r for r in records if r["status"] == 200]
    log(f"window: {len(records)} queries sent in {seconds} s, "
        f"{len(done)} answered, "
        f"{compiles.summary(win['t_begin'], win['t_end'])}")
    log("window by tenths: " + "; ".join(
        window_tenth(done, compiles, win["t_begin"], float(seconds), k)
        for k in range(10)))

    # -- correctness, once the window is closed and the service stopped
    t_ref = time.monotonic()
    table, ref_plan = reference.build_table(
        ds.traces, int(gen["interval_ns"]), int(gen["join_window_ns"]),
        int(gen["join_cap"]), int(config["n_ranks"]))
    numbers = reference.empty_numbers()
    notes: List[str] = []
    numbers["unanswered"] += len(records) - len(done)
    lat = [r["t_done"] - r["t_send"] for r in done]
    must = []
    if done:
        must = [max(range(len(done)), key=lambda i: lat[i]),
                max(range(len(done)), key=lambda i: done[i]["rows_scanned"])]
    for i in workload.sample_indices(seed, len(done), int(mix["sample"]),
                                     must):
        rec = done[i]
        want = reference.answer(table, ref_plan, rec["spec"])
        reference.compare(win["answers"].get(rec["digest"]), want, numbers,
                          notes, tag=f"query {json.dumps(rec['spec'])}")
    del table
    log(f"reference: {time.monotonic() - t_ref:.3f} s")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    mctx = types.SimpleNamespace(
        records=records, done=done, window=win, compiles=compiles,
        reduces=recorder.reduces if recorder else [],
        n_devices=len(devices), device_kind=devices[0].device_kind,
        trace=None, setup_s=setup_s, seconds=float(seconds))
    return finish(ctx, mctx, device, numbers, reference.LIMITS, notes,
                  trace, trace_dir, attempted=len(records),
                  failed=len(records) - len(done))


def window_tenth(done: List[Dict], compiles: Compiles, t_begin: float,
                 seconds: float, k: int) -> str:
    """Queries sent in the k-th tenth of the window: how many, their
    median latency, how many missed the caches, the summary evictions
    their ticks reported (a tick's count once for each query it
    answered), and the compiles that started in that tenth."""
    t0 = t_begin + seconds * k / 10
    t1 = t_begin + seconds * (k + 1) / 10
    lat = sorted(r["t_done"] - r["t_send"] for r in done
                 if t0 <= r["t_send"] < t1)
    p50 = workload.nearest_rank(lat, 0.5)
    p50s = f"{p50 * 1e3:.0f}" if p50 is not None else "-"
    misses = sum(1 for r in done if t0 <= r["t_send"] < t1
                 and not r["cache_hit"] and not r["inflight_hit"])
    evicted = sum(r.get("evicted", 0) for r in done
                  if t0 <= r["t_send"] < t1)
    return (f"{len(lat)} q p50 {p50s} ms, {misses} misses, {evicted} "
            f"evictions in their ticks, {compiles.summary(t0, t1)}")


def finish(ctx: Dict, mctx, device: Dict, numbers: Dict, limits: Dict,
           notes: List[str], trace: bool, trace_dir: str, attempted: int,
           failed: int) -> Dict:
    """The result object: metrics, device, breakdown, and the numbers
    compared (their key last)."""
    result: Dict = {"correct": reference.verdict(numbers, limits),
                    "attempted": int(attempted), "failed": int(failed)}
    metrics: Dict[str, Dict] = {}
    if trace:
        import trace_reduce
        pd = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        red = trace_reduce.reduce(pd)
        mctx.trace = red
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        result["breakdown"] = trace_reduce.breakdown(red)
        for m in ctx["per_layer"]:
            v = metric_reader(m["name"])(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in ctx["end_to_end"]:
            v = metric_reader(m["name"])(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    for n in notes:
        log(f"mismatch: {n}")
    for line in reference.lines(numbers, limits):
        log(f"compared {line}")
    result["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                          for k in limits}
    return result


# --- entry point ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    ctx = load_cell(load_benchmark(), args.workload)
    if not chip_visible():
        log("bench: no TPU on this host (no accelerator device files, or "
            "JAX_PLATFORMS excludes tpu); no result")
        return 2
    work = os.path.join(WORK, args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        from live import run_live
        runner = {"explore": run_explore, "live": run_live}
        result = runner[ctx["mix"]["kind"]](
            ctx, args.seed, args.seconds, bool(args.trace), work)
    except NoChip as e:
        log(f"bench: {e}; no result")
        return 2
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
