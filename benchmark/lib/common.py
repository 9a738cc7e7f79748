"""What every cell kind shares: the run's context, device facts, the compile
cache, the profiler window and the result line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time


def log(msg: str) -> None:
    """An earlier line of the output (the result is the LAST line)."""
    print(msg, flush=True)


class Ctx:
    """One run of one cell."""

    def __init__(self, bench, cell: dict, cfg: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, t_process: float,
                 rehearse: bool):
        self.bench = bench
        self.cell = cell
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(seed)
        self.seed32 = int(seed) % (2 ** 31 - 1)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process = t_process
        self.rehearse = rehearse
        self.chips = int(cell["chips"])
        self.out_dir = os.path.join(bench.root, ".bench_out", cell["name"])
        # what the per-layer readers read
        self.counters: dict = {}
        self.spans: dict = {}
        self.e2e: dict = {}
        self.notes: dict = {}       # printed beside the metrics, unbounded
        self.trace_data = None
        self.trace_window_s = None
        self.peaks = None
        self.checks: list = []

    def check(self, name: str, value: float, limit: float) -> bool:
        """One number compared beside its limit; printed in every run."""
        ok = bool(value <= limit) and value == value
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": ok})
        log(f"CHECK {name}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if ok else 'NOT CORRECT'}")
        return ok


def load_cell(bench, workload: str, rehearse: bool):
    """(cell, config, traffic) of one workload; under --rehearse the tiny
    sizes of benchmark/rehearse.json on the CPU, Pallas interpreted."""
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        with open(os.path.join(bench.dir, "rehearse.json")) as f:
            tiny = json.load(f)
        cfg.update(tiny["config"])
        for k, v in tiny.get("config_nested", {}).items():
            if k in cfg:
                cfg[k].update(v)
        traffic.update(tiny["traffic"].get(traffic["kind"], {}))
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell['chips']}")
    return cell, cfg, traffic


def check_weights_fit(params: dict, weights: dict) -> None:
    """The reference's seeded weights must have exactly the names, shapes and
    types of the program's parameters they take the place of."""
    want = {k: (v.shape, str(v.dtype)) for k, v in params.items()}
    got = {k: (v.shape, str(v.dtype)) for k, v in weights.items()}
    if want != got:
        raise RuntimeError("the reference's weights do not fit the program's "
                           f"parameters: {set(want.items()) ^ set(got.items())}")


def setup_jax(ctx: Ctx):
    """Import JAX, place the compile cache, count its hits, insist on the
    chips the cell asks for.  Returns (jax, device dict)."""
    import jax

    from paddle_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = {"hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name.endswith("/cache_hits"):
            events["hits"] += 1
        elif name.endswith("/cache_misses"):
            events["misses"] += 1

    # every backend compile and every jaxpr trace of the process, watched
    # site or not (an eager op with a new shape compiles too)
    jit_work = {"backend_compiles": 0, "backend_compile_s": 0.0,
                "traces": 0, "trace_s": 0.0}

    def on_duration(name, secs, **kw):
        if name.endswith("/backend_compile_duration"):
            jit_work["backend_compiles"] += 1
            jit_work["backend_compile_s"] += secs
        elif name.endswith("/jaxpr_trace_duration"):
            jit_work["traces"] += 1
            jit_work["trace_s"] += secs

    try:
        import jax.monitoring as mon
        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)
    except Exception:           # noqa: BLE001 — counting hits is a courtesy
        pass
    ctx.counters["compile_cache"] = events
    ctx.counters["jit_work"] = jit_work
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"DEVICE {json.dumps(device)} compile cache at {cache_dir}")
    from .spec import peaks_for
    if not ctx.rehearse:
        if device["platform"] != "tpu":
            print(f"benchmark: no TPU (platform {device['platform']!r})",
                  file=sys.stderr)
            sys.exit(2)
        if len(devs) < ctx.chips:
            print(f"benchmark: the cell asks for {ctx.chips} chips, JAX "
                  f"sees {len(devs)}", file=sys.stderr)
            sys.exit(2)
    # a rehearsal's shares are never reported; it borrows the v5e row
    ctx.peaks = peaks_for("TPU v5 lite" if ctx.rehearse else device["kind"],
                          ctx.bench.dir)
    device["count"] = ctx.chips if len(devs) >= ctx.chips else len(devs)
    return jax, device


def memory_bytes(jax, chips: int, stat: str = "peak_bytes_in_use") -> int:
    """A memory statistic of the fullest chip: the peak, or `bytes_in_use`
    for what is resident now."""
    return max(int((d.memory_stats() or {}).get(stat, 0))
               for d in jax.devices()[:chips])


def compiles_by_site() -> dict:
    from paddle_tpu.obs.compile_watch import get_compile_watch

    return {site: s["compiles"]
            for site, s in get_compile_watch().snapshot().items()}


def compiles_total() -> int:
    return sum(compiles_by_site().values())


class ProfilerWindow:
    """jax.profiler around a slice of the window; then the reduction.  The
    slice is stamped INTO the trace, as a host event of the benchmark's own
    (lib/trace.py: WINDOW_EVENT) opened once the profiler has started and
    closed before it is stopped: the profiler's stop takes a minute with the
    device still stepping, and every reduction is of the stamped interval."""

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        self.dir = os.path.join(ctx.out_dir, "trace")
        self.t0 = self.host_s = self.event = None

    def start(self):
        import jax

        from .trace import WINDOW_EVENT

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.event = jax.profiler.TraceAnnotation(WINDOW_EVENT)
        self.event.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        """On the thread that called start(): the event nests on it."""
        import jax

        self.host_s = time.perf_counter() - self.t0
        self.event.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        from .trace import Trace, TraceError, find_xplane

        t = time.perf_counter()
        tr = Trace.from_xplane(find_xplane(self.dir),
                               cpu_as_device=self.ctx.rehearse)
        if tr.window_event() is None:
            raise TraceError("the trace holds no window event: "
                             f"{json.dumps(tr.describe())[:1500]}")
        self.ctx.trace_data = tr
        self.ctx.trace_window_s = tr.window_s
        log(f"TRACE planes {json.dumps(tr.describe())[:1500]} "
            f"(read in {time.perf_counter() - t:.1f}s)")
        # what the trace file holds whenever it ran: its device planes alone
        whole = Trace({p: tr.planes[p] for p in tr.device_planes()})
        log(f"TRACE window {tr.window_s:.6f}s on the trace's clock, "
            f"{self.host_s:.6f}s on the host's; device busy "
            f"{tr.busy_s():.6f}s in it, {whole.busy_s():.6f}s in the whole "
            f"file's {whole.window_s:.6f}s")
        return tr
