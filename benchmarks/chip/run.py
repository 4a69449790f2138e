#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a ``workloads`` entry of ``BENCHMARK.json`` at the checkout root:
a configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``); the mix's ``mode`` names the module that
runs it (``<mode>_loop.py``: ``serve`` or ``train``).  The run makes the
weights and the traffic from ``--seed``, warms up every program the window
calls (set-up), drives the window for ``--seconds``, then checks what the
window produced against the configuration's plain reference.  Metrics are read by their own files,
``metrics/<metric>.py``: the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1`` (which also profiles the last
seconds of the window).

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and ``breakdown`` when traced); the
numbers compared, each beside its limit, are the last lines of stderr and
the result's last key.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

TRACE_DIR = ROOT / ".bench_trace"
LOWP = "float8_e4m3fn"       # the control's precision: below bfloat16


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metric_names(workload: str, trace: bool) -> list:
    b = benchmark()
    group = b["per_layer"] if trace else b["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``: a window that
    traces or compiles anything called a shape that set-up did not warm."""

    def __init__(self):
        import jax
        self.on = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on:
            self.traces += event == "/jax/core/compile/jaxpr_trace_duration"
            self.compiles += \
                event == "/jax/core/compile/backend_compile_duration"


class Profiler:
    """Profiles the last ``length`` seconds of the window of a traced run;
    a ``bench.window`` span marks the traced slice on the host clock."""

    def __init__(self, seconds: float, length: float):
        self.start_at = max(0.0, seconds - length)
        self.on = False
        self.span = None

    def __call__(self, now: float) -> None:
        import jax
        if not self.on and now >= self.start_at:
            TRACE_DIR.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


@dataclasses.dataclass
class Cell:
    """A workload resolved by name: its configuration file, its mix, the
    program's configuration, model and builder.  A mode's ``Job`` is set
    up from it."""
    workload: dict
    conf: dict
    tr: dict
    cfg: object
    api: object
    builder: object
    rehearsal: bool

    def log(self, msg: str) -> None:
        log(msg)


def load_cell(workload: dict, sizes: dict | None = None,
              tr: dict | None = None) -> Cell:
    """``sizes`` shrinks the configuration, and the cell's own mix with it,
    for the CPU rehearsal (tests); ``tr`` stands in for the cell's mix as
    given (the knee sweep's rates)."""
    import model
    import traffic
    from repro.models import registry
    conf = model.load_config(workload["config"])
    if tr is None:
        tr = traffic.load(workload["traffic"])
        if sizes is not None and tr["mode"] == "serve":
            tr = {**tr, **sizes_traffic(tr)}
    build = model.builder(conf)
    cfg = build.program_config(conf, **(sizes or {}))
    return Cell(workload, conf, tr, cfg, registry.build(cfg), build,
                sizes is not None)


@functools.lru_cache(maxsize=None)
def compile_counter() -> CompileCounter:
    """One listener a process, however many cells it runs."""
    return CompileCounter()


def run_cell(workload: dict, seed: int, seconds: float, trace: bool,
             sizes: dict | None = None, tr: dict | None = None,
             hook=None, t_start: float = T_START) -> dict:
    """Set up, drive and check one cell; returns the result object.

    ``sizes`` and ``tr`` as for ``load_cell``.  ``hook(job, ctx, ref,
    arch)``, where given, is called after the comparison and its return
    goes into the result as ``extra`` (the control readings and the knee
    sweep use it); ``t_start`` is when set-up began (another seed of the
    same process)."""
    import jax

    import check
    import roofline

    c = load_cell(workload, sizes, tr)
    mode = importlib.import_module(f"{c.tr['mode']}_loop")
    dev = jax.devices()[0]
    pk = None if c.rehearsal else roofline.peaks(dev.device_kind)
    ctx = {"cfg": c.cfg, "tr": c.tr, "peaks": pk, "trace": None}

    t_mark = [time.perf_counter()]

    def mark(what: str) -> None:
        now = time.perf_counter()
        log(f"set-up: {what} {now - t_mark[0]:.2f}s")
        t_mark[0] = now

    mark("imports and config")
    counter = compile_counter()
    counter.traces = counter.compiles = 0
    prof = Profiler(seconds, c.tr.get("trace_seconds", 3.0)) if trace \
        else None
    job = mode.Job(c, seed, seconds, mark)
    ctx["setup_s"] = time.perf_counter() - t_start
    log(f"set-up {ctx['setup_s']:.1f}s; window {seconds}s")
    counter.on = True
    out = job.run(seconds, on_time=prof)
    counter.on = False
    log(f"window: {counter.traces} traces, {counter.compiles} compiles")
    if prof is not None:
        prof.stop()
    ctx.update(out)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    if trace:
        import trace as trace_mod
        ctx["trace"] = trace_mod.reduce(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in metric_names(workload["name"], trace):
        value = (ctx["setup_s"] if m["name"] == "setup_s"
                 else reader(m["name"])(ctx))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ---- the comparison, once the program's state is freed
    ref = check.reference(c.conf["reference"])
    arch = ref.Arch.of(c.conf, **arch_sizes(c.cfg)) if c.rehearsal \
        else ref.Arch.of(c.conf)
    limits = c.conf["limits"][c.tr["mode"]]
    t = time.perf_counter()
    numbers = job.compare(ref, arch)
    log(f"check in {time.perf_counter() - t:.1f}s")
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in compared.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": 0, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count(),
                         "memory_peak_bytes": peak}}
    if trace and ctx["trace"] is not None:
        tr_ = ctx["trace"]
        result["device"]["busy_s"] = tr_["busy_s"]
        result["device"]["window_s"] = tr_["window_s"]
        result["breakdown"] = {"device_ops": tr_["device_ops"],
                               "idle_gaps": tr_["idle_gaps"]}
    if hook is not None:
        result["extra"] = hook(job, ctx, ref, arch)
    result["check"] = compared
    return result


def reader(name: str):
    import named
    return named.module("metrics", name).read


def sizes_traffic(tr: dict) -> dict:
    """A serving mix cut to the rehearsal's tiny pool."""
    arr = tr["arrivals"]
    if "in_flight" in arr:
        arr = {**arr, "in_flight": 2}
    return {"arrivals": arr, "pool": {"slots": 4, "cache_len": 64},
            "prompt": {**tr["prompt"], "min": 4, "max": 24, "median": 8},
            "output": {**tr["output"], "min": 2, "max": 24, "median": 8},
            "tasks": {**tr["tasks"], "count": 6, "resident_rows": 3}}


def arch_sizes(cfg) -> dict:
    return {"d": cfg.d_model, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "layers": cfg.n_layers}


def on_chip(w: dict) -> bool:
    """Whether JAX finds a TPU with the chips cell ``w`` asks for; if so,
    turns on the persistent compile cache."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"needs a TPU; JAX found platform {devs[0].platform!r}")
        return False
    if len(devs) < w["chips"]:
        log(f"{w['name']} needs {w['chips']} chips; JAX found {len(devs)}")
        return False
    from repro.dist import backend
    backend.enable_compile_cache()
    # every program goes to the cache, however fast it compiled, so that a
    # checkout's second run loads all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    w = cell(args.workload)
    if not on_chip(w):
        return 2
    result = run_cell(w, args.seed, args.seconds, bool(args.trace))
    for k, v in result["check"].items():
        log(f"check {k}: {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
