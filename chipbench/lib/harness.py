"""One run of one cell: set-up, the measured window, the check.

The cell is found by name in ``BENCHMARK.json``.  Its configuration file
gives the model's sizes and the codec, its traffic file the mix, its
limits file the numbers ``correct`` compares and their limits.  Per-layer
metrics are read by ``chipbench/metrics/<name>.py``.

Set-up makes the weights on the device (from the configuration's
``weights_seed``: one model is served, whatever the run's seed) and the
frame pool on the host, builds the program's ``CellSimulator`` with a
fixed option, and plays one round, which compiles (or loads from the
compile cache) every program the window drives.  The window is a closed loop of rounds: one
``run_stream`` call per round, every UE capturing one frame at the same
instant, ended by ``block_until_ready`` on that round's detections.  It
ends at the first round boundary after ``seconds``; every rate is over
the time from its start to that boundary.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import model, spans as spans_mod, trace as trace_mod
from chipbench.lib.model import Shape
from chipbench.lib.traffic import Schedule, Traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MAX_WAIT_S = 30.0        # a tail batch closes only once its bucket is full
SAMPLE_FRAMES = 16       # frames of the window the check compares
MAX_DEPARTURE = 0.10     # a traced part's pace against the untraced


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic: Traffic
    limits: Dict[str, Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _for_cell(metrics: Sequence[Dict[str, Any]], cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> CellSpec:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", wl["traffic"] + ".json")) as f:
        traffic = Traffic.from_file(json.load(f))
    with open(os.path.join(BENCH_DIR, "limits", name + ".json")) as f:
        limits = json.load(f)
    return CellSpec(name, wl["chips"], wl["config"], config, traffic, limits,
                    _for_cell(bench["end_to_end"], name),
                    _for_cell(bench["per_layer"], name))


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def require_chips(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX found {len(devs)}")


def enable_compile_cache():
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(name: str, cfg: Dict[str, Any]):
    from repro.configs.swin_t_detection import SwinConfig
    kw = {f.name: (tuple(cfg[f.name]) if isinstance(cfg[f.name], list)
                   else cfg[f.name])
          for f in dataclasses.fields(SwinConfig)
          if f.name in cfg}
    return SwinConfig(name=name, **kw)


@dataclasses.dataclass
class Round:
    ok: bool                          # full batches, every frame served
    outs: Dict[int, Any]              # UE -> detections
    frames: List[int]                 # pool index per UE
    compressed_bytes: int
    seconds: float
    gc_seconds: float = 0.0           # in full collections, while it ran


class Cell:
    """The program's cell, built as the traffic file says, with the
    benchmark's weights and frames."""

    def __init__(self, spec: CellSpec, params, pool: List[np.ndarray],
                 seed: int, recorder: Optional[spans_mod.Recorder] = None):
        from repro.core.calibration import calibrate
        from repro.core.cell import CellSimulator
        from repro.core.compression import ActivationCodec
        from repro.core.ran import RanCell, RanConfig, make_policy
        from repro.core.splitting import SwinSplitPlan

        t = spec.traffic
        codec = spec.config["codec"]
        ran = None
        if t.mac is not None:
            ran = RanCell(make_policy(t.mac["policy"]),
                          RanConfig(n_prbs=t.mac["n_prbs"],
                                    tti_s=t.mac["tti_s"]))
        self.t = t
        self.pool = pool
        self.schedule = Schedule(t, seed)
        self.rec = recorder
        self.sim = CellSimulator(
            plan=SwinSplitPlan(program_config(spec.config_name, spec.config),
                               params),
            system=calibrate(), n_ues=t.n_ues,
            codec=ActivationCodec(quant_block=codec["quant_block"],
                                  level=codec["level"], mode=codec["mode"]),
            controller=None, execute_model=True, buckets=t.buckets,
            max_wait_s=MAX_WAIT_S, ran=ran, engine="python")

    def play(self, r: int) -> Round:
        frames, levels, sim_seed = self.schedule.round(r)
        imgs = [self.pool[i] for i in frames]
        self.sim.seed = sim_seed
        t0 = time.perf_counter()
        traced = self.rec is not None and self.rec.on
        with self.rec.span("loop") if traced else contextlib.nullcontext():
            res = self.sim.run_stream(levels[None, :], imgs=imgs,
                                      option=self.t.option, keep_outputs=True)
            outs = res.outputs[0]
            jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        n, batch = self.t.n_ues, self.t.buckets[-1]
        ok = (res.stats.n_batches == n // batch and len(res.logs) == n
              and all(l.batch_size == batch and not l.dropped
                      for l in res.logs)
              and sorted(outs) == list(range(n))
              and all(outs[u] is not None for u in outs))
        return Round(ok, outs, frames,
                     sum(int(l.compressed_bytes) for l in res.logs), dt)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def rel_l2(out, ref, key: str = "cls") -> float:
    """||out - ref|| / ||ref - mean(ref)|| of one output map (``cls``: the
    class scores) over every FPN level of one frame: how far the served
    map lies from the reference's, against the reference's own spread."""
    a = np.concatenate([np.asarray(l[key], np.float64).ravel() for l in out])
    b = np.concatenate([np.asarray(l[key], np.float64).ravel() for l in ref])
    return float(np.linalg.norm(a - b) / np.linalg.norm(b - b.mean()))


def finite(out) -> bool:
    return all(np.all(np.isfinite(np.asarray(l[k])))
               for l in out for k in ("cls", "box", "ctr"))


class Sample:
    """A uniform sample, drawn from the seed, of the window's frames
    (reservoir of ``k``): their detections stay on the device until the
    window closes."""

    def __init__(self, k: int, seed: int):
        self.k, self.n = k, 0
        self.rng = np.random.default_rng([seed % 2 ** 32, seed // 2 ** 32, 1])
        self.items: List[Tuple[int, Any]] = []

    def offer(self, pool_idx: int, out):
        self.n += 1
        if len(self.items) < self.k:
            self.items.append((pool_idx, out))
            return
        j = int(self.rng.integers(self.n))
        if j < self.k:
            self.items[j] = (pool_idx, out)


def check(spec: CellSpec, shape: Shape, params, pool, sample: Sample
          ) -> Tuple[Dict[str, Dict[str, float]], int]:
    """Compare the sampled detections with the reference.  Returns the
    numbers compared, each with its limit, and how many sampled frames
    had no finite detections."""
    split = spec.traffic.split
    block = spec.config["codec"]["quant_block"]
    fwd = jax.jit(lambda p, img: model.reference_forward(
        shape, p, img, split, block))
    outs = [(i, jax.device_get(o)) for i, o in sample.items]
    sample.items = []
    refs = {}
    for i in sorted({i for i, _ in outs}):
        refs[i] = jax.device_get(fwd(params, jnp.asarray(pool[i])))
    bad = sum(0 if finite(o) else 1 for _, o in outs)
    worst = max((rel_l2(o, refs[i]) for i, o in outs), default=math.inf)
    lim = spec.limits["cls_rel_l2"]["limit"]
    return {"cls_rel_l2": {"value": worst, "limit": lim}}, bad


# ---------------------------------------------------------------------------
# per-layer reading
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceContext:
    """What a per-layer reader reads: the layer spans of the span part,
    the device trace of the device part, and what each part of the
    window did."""
    spans: List[spans_mod.Span]       # the span part's
    missing: set
    wrapped: set
    frames: int                       # completed in the span part
    trace: Optional[trace_mod.Trace]  # the device part's
    window_ns: Optional[Tuple[float, float]]
    rounds: int                       # played in the device part
    plain_frames: int                 # the untraced part
    plain_seconds: float
    shape: Shape
    traffic: Traffic
    quant_block: int
    peaks: Dict[str, float]

    def per_frame_ms(self, name: str) -> Optional[float]:
        """Summed seconds of the outermost spans called ``name``, in ms
        per frame completed."""
        if name in self.missing or name not in self.wrapped or not self.frames:
            return None
        s = sum(x.seconds for x in self.spans
                if x.name == name and x.parent != name)
        return 1e3 * s / self.frames


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the backend compiles JAX makes while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.on = 0, False

    def _event(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)
        return False


class GcClock:
    """Seconds the interpreter spends in collections of its oldest
    generation, which stop every Python thread."""

    def __init__(self):
        self.seconds = 0.0
        self._t0: Optional[float] = None

    def _callback(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)
        return False


@dataclasses.dataclass
class Part:
    """Whole rounds of the window, played one way."""
    name: str
    rounds: List[Round]
    t0: float
    t1: float

    @property
    def rounds_per_s(self) -> float:
        return len(self.rounds) / (self.t1 - self.t0)

    @property
    def pace(self) -> float:
        """Rounds per second at the median round: a round that one host
        stall stretched moves it little, a cost on every round moves it
        in full."""
        return 1.0 / statistics.median(rd.seconds for rd in self.rounds)


@dataclasses.dataclass
class Window:
    parts: List[Part]

    @property
    def rounds(self) -> List[Round]:
        return [rd for p in self.parts for rd in p.rounds]

    @property
    def seconds(self) -> float:
        return self.parts[-1].t1 - self.parts[0].t0

    def part(self, name: str) -> Optional[Part]:
        return next((p for p in self.parts if p.name == name), None)


class Tracer:
    """The traced parts of a ``--trace 1`` window, which follow an
    untraced part that runs as a ``--trace 0`` run does.  In the span
    part, played where a metric of the cell reads spans, the readers'
    wraps record the layer spans, and no profiler runs.  In the device
    part the profiler records the chip's operations,
    with the host tracer off (it records every host-side transpose of an
    uploaded frame, and that slows each upload by tens of ms); the wraps
    stay on there, none of them waiting for the device, to say what the
    host was doing in each idle gap."""

    def __init__(self, wraps, spans: bool):
        self.wraps = wraps
        self.parts = ("spans", "device") if spans else ("device",)
        self.rec = spans_mod.Recorder()
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.missing: set = set()
        self.clock: Optional[Tuple[float, int]] = None
        self._restore = lambda: None
        self._profiling = False

    def begin(self, part: str):
        wraps = self.wraps
        if part == "device":
            wraps = [(n, m, p, False) for n, m, p, _ in wraps]
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._profiling = True
            self.clock = (time.perf_counter(), time.time_ns())
        missing, self._restore = spans_mod.install(wraps, self.rec)
        if part == "spans":
            self.missing = missing
        self.rec.on = True

    def end(self, part: str):
        self.rec.on = False
        self._restore()
        if part == "device":
            jax.profiler.stop_trace()
            self._profiling = False

    def to_trace_ns(self, t: float, trace_start_ns: int) -> float:
        """A ``perf_counter`` reading of the device part on the trace's
        clock (ns from the profiler's start)."""
        perf0, wall0 = self.clock
        return wall0 - trace_start_ns + (t - perf0) * 1e9

    def close(self):
        self.rec.on = False
        self._restore()
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
        shutil.rmtree(self.dir, ignore_errors=True)


def measure(cell: Cell, seconds: float, sample: Sample,
            compiles: CompileCounter, gc_clock: GcClock,
            tracer: Optional[Tracer] = None,
            trace_seconds: float = 0.0) -> Window:
    """Rounds from now to the first round boundary after ``seconds``.
    With a ``tracer`` the last of them are its parts, ``trace_seconds``
    each, every part whole rounds."""
    plan = [("plain", seconds)]
    if tracer:
        k = len(tracer.parts)
        plan = [("plain", seconds - k * trace_seconds)] + [
            (name, seconds - (k - 1 - i) * trace_seconds)
            for i, name in enumerate(tracer.parts)]
    parts: List[Part] = []
    compiles.on = True
    t0 = time.perf_counter()
    r = 1
    for name, until in plan:
        if name != "plain":
            tracer.begin(name)
        part = Part(name, [], time.perf_counter(), 0.0)
        while not part.rounds or time.perf_counter() - t0 < until:
            gc0 = gc_clock.seconds
            rd = cell.play(r)
            rd.gc_seconds = gc_clock.seconds - gc0
            r += 1
            part.rounds.append(rd)
            for u, out in rd.outs.items():
                if out is not None:
                    sample.offer(rd.frames[u], out)
            rd.outs = None
        part.t1 = time.perf_counter()
        if name != "plain":
            tracer.end(name)
        parts.append(part)
    compiles.on = False
    return Window(parts)


def tenths(rounds: Sequence[Round]) -> List[float]:
    """Rounds per second of each tenth of the rounds, in order: where a
    slow run lost its time."""
    k = len(rounds)
    cuts = [round(i * k / 10) for i in range(11)]
    return [(b - a) / sum(rd.seconds for rd in rounds[a:b])
            for a, b in zip(cuts, cuts[1:]) if b > a]


def end_to_end(spec: CellSpec, win: Window, setup_s: float
               ) -> Dict[str, Dict[str, Any]]:
    rounds, n = win.rounds, spec.traffic.n_ues
    done = sum(n for rd in rounds if rd.ok)
    values = {
        "frames_per_s": done / win.seconds,
        # a round of a one-camera cell is one frame
        "frame_ms.p95": (statistics.quantiles([rd.seconds * 1e3
                                               for rd in rounds], n=20)[-1]
                         if len(rounds) >= 2 and n == 1 else None),
        "uplink_bytes_per_frame": (sum(rd.compressed_bytes for rd in rounds)
                                   / (n * len(rounds))),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end}


def departure(part: Part, plain: Part) -> float:
    """How far a part's pace lies from the untraced part's, as a share of
    the latter."""
    return part.pace / plain.pace - 1.0


def per_layer(spec: CellSpec, win: Window, tracer: Tracer, readers,
              shape: Shape, peaks: Dict[str, float]):
    """(metrics, device busy and window seconds, breakdown) of the traced
    parts of the window.  A part whose pace (``Part.pace``) departs from
    the untraced part's by more than ``MAX_DEPARTURE`` does not describe
    the system that was timed: its metrics read nothing."""
    n = spec.traffic.n_ues
    plain, span_part, dev_part = (win.part(p) for p in
                                  ("plain", "spans", "device"))
    for p in filter(None, (span_part, dev_part)):
        _log(f"{p.name} part: {len(p.rounds)} rounds over "
             f"{p.t1 - p.t0:.3f} s ({p.rounds_per_s:.4f} rounds/s), pace "
             f"{p.pace:.4f} rounds/s against {plain.pace:.4f} untraced "
             f"({100 * departure(p, plain):+.2f}%)")
    spans_ok = (span_part is not None
                and abs(departure(span_part, plain)) <= MAX_DEPARTURE)
    dev_ok = abs(departure(dev_part, plain)) <= MAX_DEPARTURE
    tr = trace_mod.load(tracer.dir) if dev_ok else None
    win_ns = host = None
    if tr is not None and tr.start_ns is not None:
        win_ns = (tracer.to_trace_ns(dev_part.t0, tr.start_ns),
                  tracer.to_trace_ns(dev_part.t1, tr.start_ns))
        host = [trace_mod.Op(x.name, tracer.to_trace_ns(x.t0, tr.start_ns),
                             tracer.to_trace_ns(x.t1, tr.start_ns))
                for x in tracer.rec.spans
                if x.t0 >= dev_part.t0 and x.t1 <= dev_part.t1]
    ctx = TraceContext(
        spans=([x for x in tracer.rec.spans
                if x.t0 >= span_part.t0 and x.t1 <= span_part.t1]
               if spans_ok else []),
        missing=tracer.missing, wrapped={w[0] for w in tracer.wraps},
        frames=(sum(n for rd in span_part.rounds if rd.ok)
                if spans_ok else 0),
        trace=tr, window_ns=win_ns, rounds=len(dev_part.rounds),
        plain_frames=sum(n for rd in plain.rounds if rd.ok),
        plain_seconds=plain.t1 - plain.t0, shape=shape, traffic=spec.traffic,
        quant_block=spec.config["codec"]["quant_block"], peaks=peaks)
    metrics = {}
    for m in spec.per_layer:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"busy_s": 0.0, "window_s": dev_part.t1 - dev_part.t0}
    breakdown = {"device_ops": [], "idle_gaps": []}
    if win_ns is not None:
        device["busy_s"] = trace_mod.busy_ns(tr.ops, win_ns) * 1e-9
        breakdown = {
            "device_ops": trace_mod.top_ops(tr.ops, win_ns),
            "idle_gaps": trace_mod.labelled_gaps(tr.ops, host, win_ns)}
    _log(f"untraced part: {len(plain.rounds)} rounds over "
         f"{plain.t1 - plain.t0:.3f} s; device part: "
         f"{len(tr.ops) if tr else 0} device ops; spans missing: "
         f"{sorted(tracer.missing)}")
    return metrics, device, breakdown


def run(spec: CellSpec, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True,
        peaks: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """One run of the cell.  Returns the result line's object."""
    if seed < 0:
        raise ValueError("--seed must be a whole number >= 0")
    import repro.core.cell  # noqa: F401  (fail before set-up without it)
    if require_tpu:
        require_chips(spec.chips)
        enable_compile_cache()
    dev = jax.devices()[0]
    peaks = peaks or load_peaks(dev.device_kind)
    shape = Shape.from_config(spec.config)
    t = spec.traffic

    readers, tracer = {}, None
    if traced:
        readers = {m["name"]: load_reader(m["name"]) for m in spec.per_layer}
        tracer = Tracer([w for r in readers.values() for w in r.WRAPS],
                        any(m["source"] == "program_span"
                            for m in spec.per_layer))
    try:
        with CompileCounter() as compiles, GcClock() as gc_clock:
            # -- set-up ------------------------------------------------------
            params = model.init_params(shape,
                                       spec.config["weights_seed"])
            jax.block_until_ready(params)
            pool = t.pool(shape.img_h, shape.img_w)
            cell = Cell(spec, params, pool, seed,
                        tracer.rec if tracer else None)
            if not cell.play(0).ok:
                raise RuntimeError("the warm-up round did not run as full "
                                   "batches")
            setup_s = time.perf_counter() - t_start
            _log(f"set-up {setup_s:.3f} s")
            # -- the window --------------------------------------------------
            sample = Sample(SAMPLE_FRAMES, seed)
            win = measure(cell, seconds, sample, compiles, gc_clock,
                          tracer, t.trace_seconds)
        mem = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
        n = t.n_ues
        attempted = n * len(win.rounds)
        failed = sum(0 if rd.ok else n for rd in win.rounds)
        _log(f"window {win.seconds:.3f} s, {len(win.rounds)} rounds, "
             f"{attempted} frames, {failed} failed, {compiles.n} compiles "
             f"inside the window")
        _log("pace of each tenth of the window's rounds, rounds/s: "
             + " ".join(f"{x:.4g}" for x in tenths(win.rounds)))
        slow = max(win.rounds, key=lambda rd: rd.seconds)
        _log(f"slowest round {slow.seconds:.3f} s against a median of "
             f"{statistics.median(rd.seconds for rd in win.rounds):.3f} s, "
             f"{slow.gc_seconds:.3f} s of it in full collections; "
             f"{sum(rd.gc_seconds for rd in win.rounds):.3f} s of those in "
             f"the window")
        breakdown = None
        if traced:
            metrics, busy, breakdown = per_layer(spec, win, tracer, readers,
                                                 shape, peaks)
            device.update(busy)
        else:
            metrics = end_to_end(spec, win, setup_s)
    finally:
        if tracer:
            tracer.close()

    # -- the check, once the window's state is freed -------------------------
    del cell, win
    kept = len(sample.items)
    checks, bad = check(spec, shape, params, pool, sample)
    correct = (bad == 0 and sample.n > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    _log(f"sample: {kept} of {sample.n} frames; {bad} without finite "
         f"detections")
    for k, c in checks.items():
        _log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
