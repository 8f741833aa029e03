"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``BENCHMARK.json`` names the cell, its
configuration file (``bench/configs/``), its traffic file
(``bench/traffic/<traffic>.json``), and its metrics, each per-layer one
read by ``bench/metrics/<name>.py``.

The window drives ``TxnService`` as a client would: a closed loop that
keeps ``outstanding`` update batches submitted and waits for the oldest
before it submits the next, and, for a mix with a reader, one read-only
scan of a pinned snapshot issued after each submit. The snapshot is
re-pinned every ``pin_hold_s`` seconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, counting, devtrace, loadgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SAMPLE_EVERY = 64           # about one batch in this many is compared
TXN_TYPE = {"10rmw": 0, "2rmw8r": 1}   # the program's YCSB branches
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


# -- the cell, from BENCHMARK.json ---------------------------------------------
@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    records: int
    payload_words: int
    batch_txns: int
    chips: int
    engine: Dict
    service: Dict
    reference: str


def load_config(name: str, path: Path) -> Config:
    data = json.loads(Path(path).read_text())
    return Config(name=name, records=int(data["records"]),
                  payload_words=int(data["payload_words"]),
                  batch_txns=int(data["batch_txns"]),
                  chips=int(data["chips"]), engine=dict(data["engine"]),
                  service=dict(data["service"]),
                  reference=data["reference"])


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Config
    mix: loadgen.Mix
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_config(conf["name"], ROOT / conf["file"])
    if config.chips != w["chips"]:
        raise ValueError(f"{name}: the cell and its configuration disagree "
                         "on the number of chips")
    mix = loadgen.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name, config, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def load_reader(metric: str) -> Callable:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- set-up pieces ---------------------------------------------------------------
class CompileLog:
    """Backend compiles (or loads from the persistent cache) as JAX
    reports them, with the time each was reported."""

    def __init__(self):
        import jax.monitoring
        self.events: List = []
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.events.append((time.perf_counter(), duration))

    def between(self, lo: float, hi: float):
        ev = [d for t, d in self.events if lo <= t < hi]
        return len(ev), float(sum(ev))

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def initial_records(seed: int, records: int, words: int):
    """[records, words] random int32 made on the device from the seed, in
    one jitted call: the store before the first transaction."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        bits = jax.random.bits(key, (records, words), jnp.uint32)
        return jax.lax.bitcast_convert_type(bits, jnp.int32)

    seed %= 1 << 64
    return make(np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def build_service(cfg: Config, mix: loadgen.Mix, devices):
    from repro.core.engine import BohmEngine
    from repro.core.workloads import make_ycsb
    from repro.runtime import cc_mesh
    from repro.service import TxnService
    mesh = cc_mesh(devices=devices) if cfg.chips > 1 else None
    eng = BohmEngine(cfg.records,
                     make_ycsb(payload_words=cfg.payload_words,
                               ops=mix.ops),
                     mesh=mesh, **cfg.engine)
    return TxnService(eng, **cfg.service)


def program_functions() -> Dict[str, List[str]]:
    """The engine's jitted-phase functions and their positional parameter
    names, by which the trace reader names their programs."""
    import inspect
    from repro.core import engine
    kinds = (inspect.Parameter.POSITIONAL_ONLY,
             inspect.Parameter.POSITIONAL_OR_KEYWORD)
    return {name: [p.name for p in inspect.signature(fn).parameters.values()
                   if p.kind in kinds]
            for name, fn in inspect.getmembers(engine, inspect.isfunction)
            if fn.__module__ == engine.__name__}


def txn_batch(hb: loadgen.HostBatch, txn_type: int):
    from repro.core.txn import TxnBatch
    t = hb.read_set.shape[0]
    return TxnBatch(hb.read_set, hb.write_set,
                    np.full((t,), txn_type, np.int32),
                    np.zeros((t, 1), np.int32))


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- the client --------------------------------------------------------------
@dataclasses.dataclass
class Window:
    """What the client saw in the window."""
    commit_lat: List[float] = dataclasses.field(default_factory=list)
    scan_lat: List[float] = dataclasses.field(default_factory=list)
    realised_txns: int = 0          # update txns realised inside the window
    submitted_txns: int = 0
    scan_reads: int = 0
    not_found: int = 0
    first: int = 0                  # first ticket of the window
    last: int = 0                   # one past the last ticket
    start: float = 0.0
    realised_at: List[float] = dataclasses.field(default_factory=list)


class Client:
    """Drives a ``TxnService`` with the cell's traffic and keeps what the
    comparison needs: the submitted history, the read values of the
    sampled batches and the reads of the sampled scans.

    Updates and scans wait in one queue in the order they were issued,
    which is the order the device runs them in, and the client blocks on
    the oldest: so each answer is timed when it is realised, and a scan
    never holds back the update stream by more than its own device time.
    Only updates count against ``outstanding``."""

    def __init__(self, svc, cell: Cell, seed: int, traced: bool = False):
        mix, cfg = cell.mix, cell.config
        self.svc, self.mix, self.traced = svc, mix, traced
        self.pool = loadgen.update_pool(seed, mix, cfg.records,
                                        cfg.batch_txns)
        self.scan_pool = loadgen.scan_pool(seed, mix, cfg.records,
                                           cfg.batch_txns)
        self.batches = [txn_batch(b, TXN_TYPE[mix.mix]) for b in self.pool]
        self.scan_batches = [txn_batch(b, 0) for b in self.scan_pool]
        self.keep_ticket = loadgen.sample_mask(seed, 0, SAMPLE_EVERY)
        self.keep_scan = loadgen.sample_mask(seed, 1, SAMPLE_EVERY)
        self.outcome = check.Outcome([], {}, [], None)
        self.queue: deque = deque()      # (kind, ..., t_issue) in issue order
        self.updates = 0                 # updates in the queue
        self.handle = None
        self.pin = 0
        self.t_pin = 0.0
        self.n_scans = 0

    def _submit(self, w: Optional[Window]) -> None:
        pos = len(self.outcome.history)
        idx = pos % len(self.pool)
        with _span("bench/submit", self.traced):
            t0 = time.perf_counter()
            ticket = self.svc.submit(self.batches[idx])
        if ticket != pos:
            raise RuntimeError(f"ticket {ticket} != submission {pos}")
        self.outcome.history.append(idx)
        self.queue.append(("update", ticket, t0))
        self.updates += 1
        if w is not None:
            w.submitted_txns += self.batches[idx].size

    def _scan(self, w: Optional[Window]) -> None:
        j = self.n_scans
        self.n_scans += 1
        idx = j % len(self.scan_batches)
        with _span("bench/read", self.traced):
            t0 = time.perf_counter()
            vals, found, _ = self.svc.run_readonly_batch(
                self.scan_batches[idx], ts=self.handle.ts)
        self.queue.append(("scan", (j, idx, self.pin, vals, found), t0))
        if w is not None:
            w.scan_reads += found.size

    def _realise_oldest(self, w: Optional[Window], t_end: float,
                        keep_last: Optional[int] = None) -> None:
        import jax
        kind, item, t0 = self.queue.popleft()
        if kind == "update":
            self.updates -= 1
            with _span("bench/wait", self.traced):
                res = self.svc.wait(item)
            t1 = time.perf_counter()
            if w is not None:
                w.commit_lat.append(t1 - t0)
                w.realised_at.append(t1)
                if t1 <= t_end:
                    w.realised_txns += res.read_vals.shape[0]
            if item == keep_last or (item < self.keep_ticket.size
                                     and self.keep_ticket[item]):
                with _span("bench/copy", self.traced):
                    self.outcome.ticket_reads[item] = np.asarray(
                        jax.device_get(res.read_vals))
            return
        j, idx, pin, vals, found = item
        with _span("bench/wait", self.traced):
            jax.block_until_ready((vals, found))
        t1 = time.perf_counter()
        found = np.asarray(found)
        if w is not None:
            w.scan_lat.append(t1 - t0)
            w.not_found += int((~found).sum())
        if j < self.keep_scan.size and self.keep_scan[j]:
            with _span("bench/copy", self.traced):
                self.outcome.scans.append(check.ScanSample(
                    pin, idx, np.asarray(jax.device_get(vals)), found))

    def _repin(self) -> None:
        with _span("bench/pin", self.traced):
            if self.handle is not None:
                self.svc.release_snapshot(self.handle)
            self.handle = self.svc.begin_snapshot()
        self.pin = len(self.outcome.history)
        self.t_pin = time.perf_counter()

    def step(self, w: Optional[Window], t_end: float) -> None:
        """One turn of the loop: submit if there is room (and scan after
        it), else wait for the oldest answer."""
        if self.updates < self.mix.outstanding:
            if self.mix.has_scans and (
                    self.handle is None or time.perf_counter() - self.t_pin
                    >= self.mix.pin_hold_s):
                self._repin()
            self._submit(w)
            if self.mix.has_scans:
                self._scan(w)
        else:
            self._realise_oldest(w, t_end)

    def drain(self, w: Optional[Window], t_end: float,
              keep_last: Optional[int] = None) -> None:
        while self.queue:
            self._realise_oldest(w, t_end, keep_last)

    def warm_up(self) -> None:
        """Every program the window runs, run once: the epoch's plan, exec
        and commit, the scan, a re-pin, the counters' transfer."""
        # one more than the loop holds: the last submit waits for the
        # oldest, as every turn of the window does
        while len(self.outcome.history) < self.mix.outstanding + 1:
            self.step(None, float("inf"))
        if self.mix.has_scans:
            self._repin()
            self._submit(None)
            self._scan(None)
        self.drain(None, float("inf"))
        self.svc.engine.metrics.snapshot()

    def run(self, seconds: float) -> Window:
        w = Window(first=len(self.outcome.history))
        with _span("bench/window", self.traced):
            t_start = w.start = time.perf_counter()
            t_end = t_start + seconds
            if self.mix.has_scans:
                self._repin()
            while time.perf_counter() < t_end:
                self.step(w, t_end)
            # answers due in the window are waited for after it closes:
            # their latency counts, their transactions not in the rate;
            # the last update submitted is always compared
            self.drain(w, t_end, keep_last=len(self.outcome.history) - 1)
        w.last = len(self.outcome.history)
        if self.handle is not None:
            self.svc.release_snapshot(self.handle)
            self.handle = None
        return w


# -- per-layer view ----------------------------------------------------------
@dataclasses.dataclass
class LayerView:
    """What a per-layer reader (``bench/metrics/<name>.py``) may read."""
    trace: Optional[devtrace.Trace]
    counters: Dict[str, float]          # deltas over the window
    commit_bytes: float                 # least bytes, the window's epochs
    hbm_bytes_per_s: float
    chips: int
    resolve_bytes_per_batch: float = 0.0  # least bytes, one scan batch

    @property
    def window(self):
        return self.trace.window()

    def module_seconds(self, function: str) -> float:
        """Device seconds of a jitted function's program in the window,
        summed over the chips."""
        lo, hi = self.window
        return sum(s.seconds for d in self.trace.devices
                   for s in devtrace.modules(d, function, lo, hi))

    def module_count(self, function: str) -> int:
        """Runs of the program on chip 0 in the window."""
        lo, hi = self.window
        return len(devtrace.modules(self.trace.devices[0], function, lo,
                                    hi))

    def busy_seconds(self) -> List[float]:
        lo, hi = self.window
        return [devtrace.busy_seconds(d, lo, hi) for d in self.trace.devices]


def _per_second(w: Window, seconds: float) -> str:
    """Update batches realised in each whole second of the window, in
    brief: a stall shows as a second far below the median."""
    n = int(seconds)
    if n < 1:
        return "no whole second in the window"
    per = np.bincount([int(t - w.start) for t in w.realised_at
                       if t - w.start < n], minlength=n)
    low = int(np.argmin(per))
    return (f"update batches realised per second of the window: median "
            f"{float(np.median(per))}, least {per[low]} in second {low}, "
            f"most {per.max()}")


def _percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values), q))


def _memory(devices, key: str) -> List[int]:
    """One of the allocator's ``memory_stats`` per chip."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get(key, 0)))
    return out


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# -- one run -------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             control: bool = False,
             patch: Optional[Callable] = None,
             compile_cache: bool = True) -> Dict:
    """Run the cell once and return the result line's object. ``patch``
    (tests only) may change the built service before set-up runs it;
    tests also leave the persistent ``compile_cache`` alone. With
    ``control`` the control's answers stand in for the program's in the
    comparison (``bench/check.py``), so the run has to come out not
    correct."""
    import jax
    from repro.runtime import setup_compile_cache

    cache_dir = "(off)"
    if compile_cache:
        # every program, however quick to compile, is kept, so that a
        # run after the first loads them all and set-up stays steady
        cache_dir = setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileLog()
    try:
        return _run(cell, seed, seconds, trace, t_process, require_tpu,
                    control, patch, cache_dir, compiles)
    finally:
        compiles.close()


def _run(cell, seed, seconds, trace, t_process, require_tpu, control,
         patch, cache_dir, compiles) -> Dict:
    import jax
    cfg, mix = cell.config, cell.mix
    devices = devices_for(cfg.chips, require_tpu)
    kind = devices[0].device_kind
    peaks = load_peaks(kind) if require_tpu else {"hbm_bytes_per_s": 1.0}

    svc = build_service(cfg, mix, devices)
    svc.engine.reset_store(initial_records(seed, cfg.records,
                                           cfg.payload_words))
    if patch is not None:
        patch(svc)
    client = Client(svc, cell, seed, traced=trace)
    client.warm_up()
    before = dict(svc.engine.metrics.snapshot())

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tmp, profiler_options=_profile_options())
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - t_process
    w = client.run(seconds)
    t_window_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    n_window, _ = compiles.between(t_setup_end, t_window_end)
    n_setup, s_setup = compiles.between(0.0, t_setup_end)
    _say(f"compile cache {cache_dir}")
    _say(f"set-up: {n_setup} programs compiled or loaded in "
         f"{s_setup:.3f} s of backend compile; set-up {setup_s:.3f} s")
    _say(f"compiles inside the window: {n_window}")
    _say(_per_second(w, seconds))

    after = dict(svc.engine.metrics.snapshot())
    counters = {k: float(np.sum(after[k]) - np.sum(before.get(k, 0)))
                for k in after if np.ndim(after[k]) == 0}
    peak = _memory(devices, "peak_bytes_in_use")
    outcome = client.outcome
    outcome.head = np.asarray(jax.device_get(svc.engine.store.base))
    pool, scan_pool = client.pool, client.scan_pool
    window_batches = [pool[i] for i in outcome.history[w.first:w.last]]
    svc.drain()
    gc.collect()
    # what the served store holds once nothing is in flight; the peak
    # above is reached in set-up (loading the store), not in the window
    resident = _memory(devices, "bytes_in_use")
    del svc, client
    gc.collect()

    init = np.asarray(jax.device_get(initial_records(
        seed, cfg.records, cfg.payload_words)))
    replay = importlib.import_module(f"bench.reference.{cfg.reference}")
    readings = check.compare(replay.Replay(init), outcome, pool, scan_pool,
                             control=control)
    del init
    correct = check.verdict(readings)

    result = {
        "correct": correct,
        "attempted": w.submitted_txns + w.scan_reads,
        "failed": w.not_found,
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": max(peak)},
    }
    if not trace:
        values = {
            "txn_per_s": w.realised_txns / seconds,
            "commit_p95_ms": 1e3 * _percentile(w.commit_lat, 95),
            "hbm_bytes_per_record_byte": sum(resident) / (
                cfg.records * cfg.payload_words * counting.WORD),
            "setup_s": setup_s,
        }
        if w.scan_lat:
            values["snapshot_read_p95_ms"] = 1e3 * _percentile(w.scan_lat,
                                                               95)
        for m in cell.end_to_end:
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    else:
        tr = devtrace.load(_xplane(tmp), program_functions())
        shutil.rmtree(tmp, ignore_errors=True)
        view = LayerView(
            trace=tr, counters=counters,
            commit_bytes=float(sum(counting.commit_bytes(
                b.n_writes, b.n_written_records,
                cfg.engine.get("ring_slots", 4), cfg.payload_words)
                for b in window_batches)),
            hbm_bytes_per_s=float(peaks["hbm_bytes_per_s"]),
            chips=cfg.chips,
            resolve_bytes_per_batch=float(counting.resolve_bytes(
                cfg.batch_txns * mix.scan_ops,
                cfg.engine.get("ring_slots", 4), cfg.payload_words)))
        for m in cell.per_layer:
            v = load_reader(m["name"])(view)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        lo, hi = tr.window()
        busy = view.busy_seconds()
        result["device"]["busy_s"] = float(np.mean(busy))
        result["device"]["window_s"] = hi - lo
        dev0 = tr.devices[0]
        idle = devtrace.gaps(devtrace.union(dev0.ops or dev0.modules, lo,
                                            hi), lo, hi)
        result["breakdown"] = {
            "device_ops": devtrace.top(devtrace.op_totals(dev0, lo, hi)),
            "idle_gaps": devtrace.top(devtrace.attribute_gaps(idle,
                                                              tr.host)),
        }
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in check.LIMITS.items()}
    checks.update({k: {"value": v} for k, v in readings.items()
                   if k not in check.LIMITS})
    result["checks"] = checks
    return result


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise RuntimeError("the profiler wrote no trace")
    return found[-1]
