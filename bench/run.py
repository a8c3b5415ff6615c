"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. The run

1. generates the deployment from ``--seed`` and loads it through
   ``GRFusion.create_table`` / ``create_graph_view``, then warms up the
   cell's own query shape through the serving loop (all of it set-up);
2. drives the window through ``GRFusion.serving_loop()``: ``QueryLoop.submit``
   / ``pump`` -> ``PreparedPlan.bind().execute()`` -> executor ->
   ``TraversalEngine`` -> backend, as the traffic's arrival process
   (``arrivals/<kind>.py``) sends the requests;
3. reads the device's memory peak, frees the engine, and checks every
   answer of the window against the query kind's plain numpy reference
   (``queries/<kind>.py``);
4. prints, as the last line of stdout, one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
   with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
   ``breakdown``, and last ``compared``: each number the check compared,
   beside its limit. Those numbers are also the last lines on stderr.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. Any traversal fault, failover, degraded or
overflowed answer, error, rejection or timeout is a failed request; one
that never comes, or comes wrong, makes ``correct`` false.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference, traffic  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.registry import Cell, Registry  # noqa: E402
from bench.trace import read_xplane, summarize  # noqa: E402
from bench.window import Window, nearest_rank  # noqa: E402

GRACE_S = 60.0  # how long past the window an answer is awaited
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_accelerator(chips: int):
    """The chips the cell runs on; refuses anything but enough TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), every program kept, so only
    the first run of a cell in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ---------------------------------------------------------------- helpers
class CompileCounter:
    """Counts compilations (and persistent-cache loads) while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def __call__(self, event, duration, **kwargs):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


@contextlib.contextmanager
def span(name: str, tracing: bool):
    if tracing:
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
    else:
        yield


def counters(eng, loop) -> Dict[str, int]:
    out = collections.Counter()
    out.update({f"traversal.{k}": v for k, v in eng.traversal.stats.items()})
    out.update({f"loop.{k}": v for k, v in loop.stats.items()})
    out.update({f"events.{k}": v for k, v in eng.events.items()})
    return out


# ------------------------------------------------------------------ check
def check_answers(cell: Cell, reg: Registry, dep, win: Window):
    """(wrong, answers compared): every answer of the window against the
    plain reference."""
    q = cell.traffic["query"]
    kind = reg.query(q["kind"])
    answered = [r for r in win.in_window if r.answered]
    expected = kind.answers(dep, q, [r.params for r in answered])
    wrong = sum(not reference.same(kind, q, r.ticket.result, e)
                for r, e in zip(answered, expected))
    return wrong, len(answered)


# ---------------------------------------------------------------- metrics
def end_to_end(cell: Cell, win: Window, setup_s: float, peak: int) -> Dict[str, Dict]:
    """The cell's end-to-end metrics. A latency percentile counts every
    request of the window, timed from when it was due; a failed one misses
    any limit. A rate counts the answered, undegraded, complete requests
    over the window's whole span."""
    lat = win.latencies_ms()
    values = {
        "setup_s": setup_s,
        "peak_hbm_gib": peak / 2 ** 30,
        "p50_ms": nearest_rank(lat, 50.0) if lat else math.nan,
        "queries_per_s": sum(1 for r in win.in_window if r.ok) / (win.t_close - win.t0),
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    return {k: {"value": values[k], "unit": units[k]} for k in units}


def per_layer(cell: Cell, reg: Registry, win: Window) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        value = reg.metric(m["name"]).read(win)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------- main
def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup(cell: Cell, reg: Registry, seed: int, seconds: float):
    """Generate, load, build the view, warm up: returns the live pieces and
    the split of set-up time."""
    from repro.core.engine import GRFusion

    split = {"start": time.monotonic() - T_PROCESS}
    t = time.monotonic()
    dep = reg.generator(cell.config["generator"]).generate(cell.config, seed)
    split["generate"] = time.monotonic() - t
    t = time.monotonic()
    eng = GRFusion(**cell.config.get("engine", {}))
    eng.create_table("V", dep.vertex)
    eng.create_table("E", dep.edge)
    jax.block_until_ready((eng.tables["V"], eng.tables["E"]))
    split["tables"] = time.monotonic() - t
    t = time.monotonic()
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst", directed=dep.directed)
    jax.block_until_ready(eng.views["G"].view)
    split["view"] = time.monotonic() - t
    t = time.monotonic()
    wl = traffic.make(reg, cell.traffic, dep, seed, seconds)
    split["traffic"] = time.monotonic() - t
    t = time.monotonic()
    query = reg.query(cell.traffic["query"]["kind"]).build(cell.traffic["query"])
    loop = eng.serving_loop()
    warm = [loop.submit(query, **p) for p in wl.warmup]
    loop.drain()
    bad = [tk for tk in warm if tk.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0].status} {bad[0].error!r}")
    split["warmup"] = time.monotonic() - t
    return dep, eng, loop, query, wl, split


def main(argv=None, *, root: Path = ROOT) -> int:
    args = parse(argv)
    reg = Registry(root)
    cell = reg.cell(args.workload)
    try:
        devices = require_accelerator(cell.chips)
    except NoAccelerator as e:
        log(f"bench: {e}")
        return 2
    if devices[0].platform == "tpu":
        peaks(devices[0].device_kind)  # an unknown chip is an error, not a default
    tracing = bool(args.trace)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(args, reg, cell, devices, tracing, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(args, reg, cell, devices, tracing, compiles) -> int:
    dep, eng, loop, query, wl, split = setup(cell, reg, args.seed, args.seconds)
    before = counters(eng, loop)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if tracing else None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - T_PROCESS
    compiles.armed = True
    with span("window", tracing):
        drive = wl.arrivals.drive(loop, query, wl, args.seconds,
                                  lambda name: span(name, tracing), GRACE_S)
    compiles.armed = False
    if tracing:
        jax.profiler.stop_trace()
    delta = collections.Counter(counters(eng, loop))
    delta.subtract(before)
    in_window = wl.arrivals.in_window(drive)
    win = Window(drive=drive, in_window=in_window,
                 missing=wl.arrivals.missing(drive, in_window),
                 counters={k: v for k, v in delta.items() if v}, grace_s=GRACE_S)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del eng, loop
    gc.collect()

    log(f"setup split (s): " + " ".join(f"{k}={v:.3f}" for k, v in split.items())
        + f" total={setup_s:.3f}")
    log(f"compiles_in_window={compiles.count}")
    log(f"counter deltas: {json.dumps(win.counters, sort_keys=True)}")
    late = [r.submitted - r.due for r in drive.requests if r.ticket is not None]
    if late:
        log(f"generator lateness (ms): max={max(late) * 1e3:.3f} "
            f"p95={nearest_rank(late, 95.0) * 1e3:.3f}")
    lat = win.latencies_ms()
    if lat:
        log("latency from due (ms): " + " ".join(
            f"p{p}={nearest_rank(lat, p):.3f}" for p in (50, 95, 99)) + f" n={len(lat)}")
    if tracing:
        try:
            paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            win.trace = summarize(read_xplane(paths[-1])) if paths else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.monotonic()
    wrong, checked = check_answers(cell, reg, dep, win)
    missing = win.missing
    log(f"reference check: {checked} answers compared in "
        f"{time.monotonic() - t:.3f} s")
    attempted = len(in_window)
    # a fault that a retry hid still failed some request of the window
    faults = sum(win.counters.get(f"events.{k}", 0)
                 for k in ("traversal_faults", "traversal_failovers"))
    failed = min(max(sum(1 for r in in_window if not r.ok), faults), attempted)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {"correct": wrong == 0 and missing == 0,
                           "attempted": attempted, "failed": failed}
    if tracing:
        out["metrics"] = per_layer(cell, reg, win)
        s = win.trace
        device["busy_s"] = s.busy_s if s else 0.0
        device["window_s"] = s.window_s if s else drive.t_end - drive.t0
        out["device"] = device
        if s is not None:
            out["breakdown"] = {"device_ops": [list(x) for x in s.top_ops],
                                "idle_gaps": [list(x) for x in s.idle_gaps]}
            log(f"device module seconds: {json.dumps(s.module_s, sort_keys=True)}")
    else:
        out["metrics"] = end_to_end(cell, win, setup_s, peak)
        out["device"] = device
    out["compared"] = {"wrong_answers": {"value": wrong, "limit": 0},
                       "missing_answers": {"value": missing, "limit": 0}}
    log(f"compared wrong_answers={wrong} limit=0")
    log(f"compared missing_answers={missing} limit=0")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    configure_compile_cache(ROOT)
    sys.exit(main())
