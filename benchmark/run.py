"""The benchmark: one cell of ``BENCHMARK.json``, one run, one result line.

    python3 benchmark/run.py --workload CELL --seed N --seconds S --trace 0|1

The cell names a configuration (``benchmark/configs/<config>.json``) and
a traffic mix (``benchmark/traffic/<traffic>.json``); the traffic names
its loop (``benchmark/loops/<loop>.py``), which generates the inputs from
``--seed``, warms every shape it will use, drives the system for
``--seconds`` and returns what it measured.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, taken with the program's
telemetry off.  With ``--trace 1`` the program records its spans, the
loop captures a profiler trace of a steady slice, and the result carries
the cell's per-layer metrics, each read by ``benchmark/metrics/<name>.py``.

Every answer of the window is compared with ``benchmark/reference.py``;
the counts compared and their limits are the last lines on standard
error and the last key of the result line.  The run exits non-zero and
prints no result when JAX finds no TPU or fewer chips than the cell
needs, when the program is not beside the benchmark, or when any
``fault.*`` counter fires.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

# Module level stays stdlib-only: the program's confirmation workers are
# spawned processes that re-import this script before they pin
# themselves to the CPU, and a JAX backend started here would take the
# chip from the parent.
import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# Import the harness as the package ``benchmark`` and the program from
# the checkout's root; the script's own directory would shadow the
# standard library's ``trace``.
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)
#: the fixed in-checkout compile cache (gitignored)
CACHE_DIR = ROOT / ".jax_cache"
#: the numbers compared, each with its limit (PERF.md gives the readings
#: each limit was set from): a wrong verdict, an ``unknown`` (no answer,
#: by the configuration's guarantees) or an answer that never came makes
#: the run incorrect
LIMITS = {"wrong_verdicts": 0, "unknown_verdicts": 0, "missing_verdicts": 0}
#: profiler options for the traced slice: host annotations only
HOST_TRACER_LEVEL = 1
#: the JAX monitoring event around every executable compiled or loaded
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Failure(Exception):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Failure(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """What a loop gets: the cell's entries and files, the seed and
    window, and the hooks that mark the window and the traced slice."""

    def __init__(self, spec: dict, name: str, seed: int, seconds: float,
                 trace: bool):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise Failure(f"no workload {name!r} in BENCHMARK.json")
        self.workload = cells[name]
        self.name = name
        self.config = json.loads(
            (BENCH / "configs" / f"{self.workload['config']}.json").read_text())
        self.traffic = json.loads(
            (BENCH / "traffic" / f"{self.workload['traffic']}.json").read_text())
        self.chips = int(self.workload["chips"])
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.window: tuple[float, float] | None = None
        self.compiles = 0
        self._compiles_at_open = 0
        self.compiles_in_window = None
        self.trace_dir: Path | None = None
        self.slice_mono: list[float] = []

    # -- the window ---------------------------------------------------
    def open_window(self) -> float:
        t = time.monotonic()
        self.window = (t, t)
        self._compiles_at_open = self.compiles
        return t

    def close_window(self) -> float:
        t = time.monotonic()
        self.window = (self.window[0], t)
        self.compiles_in_window = self.compiles - self._compiles_at_open
        return t

    # -- the traced slice ---------------------------------------------
    def start_slice(self) -> None:
        """Start the profiler (``--trace 1`` only) and mark the slice."""
        if not self.trace:
            return
        import jax

        from benchmark.trace import MARK

        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = HOST_TRACER_LEVEL
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARK):
            self.slice_mono.append(time.monotonic())

    def stop_slice(self) -> None:
        if not self.trace:
            return
        import jax

        from benchmark.trace import MARK

        with jax.profiler.TraceAnnotation(MARK):
            self.slice_mono.append(time.monotonic())
        jax.profiler.stop_trace()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, allow_cpu: bool = False) -> tuple[dict, list]:
    """The devices the cell uses; Failure unless they are TPUs (tests
    that drive a run on the CPU pass ``allow_cpu``)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Failure(f"needs a TPU, but JAX found {len(devs)} "
                      f"{devs[0].platform!r} device(s)")
    if len(devs) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX found {len(devs)}")
    used = devs[:chips]
    return ({"platform": used[0].platform, "kind": used[0].device_kind,
             "count": chips}, used)


def count_compiles(cell: Cell) -> None:
    """Count every executable JAX obtains, compiled or loaded from the
    persistent cache: there should be none inside the window."""
    import jax

    def listener(event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            cell.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def stop_children() -> None:
    """Stop the program's confirmation workers and wait for them."""
    from jepsen_tpu.parallel import batch

    pool = getattr(batch, "_CONFIRM_POOL", None)
    if pool is not None:
        pool.shutdown(wait=True, cancel_futures=True)
        batch._CONFIRM_POOL = None
    for p in multiprocessing.active_children():
        p.join(timeout=30)
        if p.is_alive():
            p.terminate()
            p.join(timeout=10)


def layer_metrics(spec: dict, cell: Cell, run: dict) -> dict:
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"benchmark_metric_{len(out)}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reduce_trace(cell: Cell, run: dict, devs) -> dict:
    """Reduce the traced slice; name idle gaps by the innermost program
    span open at their midpoint."""
    from benchmark import trace as bt

    pd = bt.load(bt.find_xplane(cell.trace_dir))
    ids = [int(d.id) for d in devs]
    red = bt.reduce(pd, devices=ids)
    # trace clock <-> monotonic clock, from the first slice mark
    m0_ns = bt.marks(pd)[0]
    mono0 = cell.slice_mono[0]
    spans = [(s["t0"], s["t0"] + s["dur"], s["name"]) for s in run["spans"]]

    def name_at(t_ns: float) -> str:
        t = mono0 + (t_ns - m0_ns) / 1e9
        inside = [(b - a, name) for a, b, name in spans if a <= t <= b]
        return min(inside)[1] if inside else "no program span"

    return {"reduced": red, "busy_s": bt.busy_s(red),
            "window_s": red["slice_s"],
            "device_ops": bt.top_ops(red),
            "idle_gaps": bt.top_gaps(red, name_at)}


def spans_of(recorder, offset: float) -> list[dict]:
    """The recording's span events, with start times on the monotonic
    clock (``t0``)."""
    return [{"name": e["name"], "t0": e["t"] + offset, "dur": e["dur"],
             "attrs": e.get("attrs") or {}}
            for e in list(recorder.events) if e.get("type") == "span"]


def run_cell(args, spec: dict, *, allow_cpu: bool = False,
             overrides: dict | None = None) -> dict:
    """One run; returns the result line.  Tests pass ``allow_cpu`` and
    ``overrides`` (``{"config": {...}, "traffic": {...}}``, merged into
    the cell's files) to drive a small run on the CPU."""
    cell = Cell(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    for part, kv in (overrides or {}).items():
        getattr(cell, part).update(kv)
    if not (ROOT / "jepsen_tpu").is_dir():
        raise Failure("the program (jepsen_tpu/) is not beside the benchmark")
    loop = load_module(BENCH / "loops" / f"{cell.traffic['loop']}.py",
                       "benchmark_loop")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    device, devs = device_info(cell.chips, allow_cpu)
    from benchmark import reference
    from jepsen_tpu import compile_cache, faults, obs

    compile_cache.enable()
    count_compiles(cell)
    log(f"cell {cell.name}: {device['kind']} x {cell.chips}, seed "
        f"{cell.seed}, {cell.seconds:g} s window, trace {int(cell.trace)}")
    with tempfile.TemporaryDirectory(prefix="benchmark-") as tmp:
        cell.trace_dir = Path(tmp) / "profile"
        with obs.recording(Path(tmp) / "telemetry", enabled=cell.trace) as rec:
            offset = time.monotonic() - rec.now() if rec is not None else 0.0
            try:
                run = loop.run(cell, devs)
            finally:
                device["memory_peak_bytes"] = memory_peak(devs)
                stop_children()
        run["spans"] = spans_of(rec, offset) if rec is not None else []
        run["window"] = cell.window
        if cell.trace and devs[0].platform == "tpu":
            run["trace"] = reduce_trace(cell, run, devs)
    fired = sorted(set(faults.fired_live()))
    if fired:
        raise Failure(f"fault counters fired: {fired}")
    counts = reference.compare(
        run["verdicts"], [reference.verdict(h, w) for h, w in run["truth"]])
    setup_s = run["setup_end"] - T_START
    log(f"setup {setup_s:.3f} s; window {cell.window[1] - cell.window[0]:.3f} s;"
        f" compiles in the window {cell.compiles_in_window}; "
        f"reference undecided {counts['undecided']}")
    for k, v in run.get("notes", {}).items():
        log(f"{k}: {v}")
    if cell.trace:
        metrics = layer_metrics(spec, cell, run)
        if "trace" in run:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
    else:
        metrics = {m["name"]: {"value": run["e2e"][m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]
                   if m["name"] != "setup_s"
                   and cell.name in m.get("workloads", [cell.name])}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    compared = {"wrong_verdicts": counts["wrong"],
                "unknown_verdicts": counts["unknown"],
                "missing_verdicts": counts["missing"]}
    line = {
        "correct": all(compared[k] <= LIMITS[k] for k in LIMITS),
        "attempted": len(run["verdicts"]),
        "failed": counts["unknown"] + counts["missing"],
        "metrics": metrics,
        "device": device,
    }
    if "trace" in run:
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    line["compared"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in compared.items()}
    for k, v in compared.items():
        log(f"compared {k}: {v} (limit {LIMITS[k]})")
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        line = run_cell(args, spec)
    except Failure as e:
        log(f"benchmark: {e}; no result printed")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
