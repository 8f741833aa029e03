"""Read the profiler's trace of a run and reduce it to intervals.

``load`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
per device plane, the events of its "XLA Modules" line (one per jitted
program run, named ``jit_<function>``) and of its "XLA Ops" line (the
operations inside them, Pallas kernels among them), and from the host
planes the benchmark's own spans (``jax.profiler.TraceAnnotation``
names that start with ``bench/``). Everything else here works on those
intervals, so the tests can build a trace by hand.

Times are seconds on the profiler's clock, which the host spans and the
device events share.

A program jitted from a ``functools.partial`` is named ``jit__unknown``
on the device (the installed JAX names the module so), whatever function
it runs. ``load`` names such a program after the function whose
positional parameters its operations read: JAX names an entry
parameter after the argument it came from (``batch_read_set`` for
``batch.read_set``), and those names show in the operations' HLO text.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
NO_SPAN = "(no bench span)"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    program: str = ""         # for an op: the function its program runs

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Span]
    ops: List[Span]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Span]

    def window(self) -> Interval:
        spans = [s for s in self.host if s.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN} span")
        return min(s.start for s in spans), max(s.end for s in spans)


def _spans(line) -> List[Span]:
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        out.append(Span(ev.name, start, start + ev.duration_ns * 1e-9))
    return out


def load(path: str, functions: Dict[str, Sequence[str]]) -> Trace:
    """The trace at ``path``; ``functions`` maps the program's jitted
    functions to their positional parameter names, to name the
    ``jit__unknown`` programs by."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:[A-Z]+:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = _spans(lines["XLA Modules"]) \
                if "XLA Modules" in lines else []
            ops = _spans(lines["XLA Ops"]) if "XLA Ops" in lines else []
            devices.append(Device(plane.name, modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(s for s in _spans(line)
                            if s.name.startswith(HOST_PREFIX))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    if devices:
        name_programs(devices, functions)
    return Trace(devices, host)


# -- naming the programs ------------------------------------------------------
UNKNOWN = re.compile(r"jit__unknown(?![A-Za-z0-9_])")
OPERAND = re.compile(r"%([A-Za-z_][A-Za-z0-9_]*)\.\d+")


def _enclosing(modules: Sequence[Span], ops: Sequence[Span]):
    """(op, module run it ran in, or None) for every op."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        yield op, (mods[i] if i >= 0 and op.start < mods[i].end else None)


def identify(operands: Iterable[str],
             functions: Dict[str, Sequence[str]]) -> Optional[str]:
    """The function whose parameters the operand names come from: the
    most parameters read, then the fewest left unread; None when no
    parameter is read or two functions tie."""
    operands = set(operands)
    scores = {}
    for fn, params in functions.items():
        read = sum(any(o == p or o.startswith(p + "_") for o in operands)
                   for p in params)
        if read:
            scores[fn] = (read, -len(params))
    if not scores:
        return None
    best = max(scores.values())
    winners = [fn for fn, sc in scores.items() if sc == best]
    return winners[0] if len(winners) == 1 else None


def name_programs(devices: List[Device],
                  functions: Dict[str, Sequence[str]]) -> None:
    """Rename ``jit__unknown(<id>)`` programs to ``jit_<function>(<id>)``
    (the id names one compiled program, the same on every chip), and
    give every op the function of the program it ran in."""
    operands: Dict[str, set] = defaultdict(set)
    runs: Dict[str, set] = defaultdict(set)
    for op, mod in _enclosing(devices[0].modules, devices[0].ops):
        if mod is None or not UNKNOWN.match(mod.name):
            continue
        # every run of a program has the same operations: a few runs of
        # each are enough, and the HLO text is long
        seen = runs[mod.name]
        if mod.start in seen or len(seen) < 4:
            seen.add(mod.start)
            operands[mod.name].update(OPERAND.findall(op.name))
    rename = {}
    for name, ops in operands.items():
        fn = identify(ops, functions)
        if fn is not None:
            rename[name] = UNKNOWN.sub(f"jit_{fn}", name, count=1)
    function: Dict[str, str] = {}
    for dev in devices:
        dev.modules = [Span(rename.get(m.name, m.name), m.start, m.end)
                       for m in dev.modules]
        ops = []
        for op, mod in _enclosing(dev.modules, dev.ops):
            name = mod.name if mod is not None else ""
            if name not in function:
                function[name] = _function(name)
            ops.append(Span(op.name, op.start, op.end, function[name]))
        dev.ops = ops


def _function(module: str) -> str:
    m = re.match(r"jit_(.*?)(?:\(|\.\d|$)", module)
    return m.group(1) if m else module


# -- interval arithmetic ------------------------------------------------------
def union(spans: Iterable[Span], lo: float, hi: float) -> List[Interval]:
    """The merged intervals the spans cover, clipped to [lo, hi]."""
    ivs = sorted((max(s.start, lo), min(s.end, hi)) for s in spans
                 if s.end > lo and s.start < hi)
    out: List[Interval] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def inside(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    """Spans that start inside [lo, hi]."""
    return [s for s in spans if lo <= s.start < hi]


# -- names --------------------------------------------------------------------
def module_pattern(function: str) -> re.Pattern:
    """A jitted function's program name: ``jit_<function>``, optionally
    followed by a suffix such as ``(123)`` or ``.1``, but not by more
    letters of a longer name."""
    return re.compile(rf"jit_{re.escape(function)}(?![A-Za-z0-9_])")


def modules(dev: Device, function: str, lo: float, hi: float) -> List[Span]:
    pat = module_pattern(function)
    return [s for s in inside(dev.modules, lo, hi) if pat.match(s.name)]


# -- reductions -----------------------------------------------------------------
def busy_seconds(dev: Device, lo: float, hi: float) -> float:
    return covered(union(dev.ops or dev.modules, lo, hi))


def attribute_gaps(idle: Sequence[Interval], host: Sequence[Span]
                   ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost benchmark span (the latest-starting one, the window itself
    excepted) that covers the gap's midpoint."""
    spans = sorted((s for s in host if s.name != WINDOW_SPAN),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]
    out: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        mid = 0.5 * (a + b)
        best: Optional[Span] = None
        # the spans do not nest deeply: the innermost cover is among the
        # last few that start before the midpoint
        i = bisect.bisect_right(starts, mid)
        for s in reversed(spans[max(0, i - 64):i]):
            if mid < s.end:
                best = s
                break
        out[best.name if best else NO_SPAN] += b - a
    return dict(out)


def top(totals: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def op_totals(dev: Device, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds by op, named ``<function>:<instruction>``."""
    out: Dict[str, float] = defaultdict(float)
    for s in inside(dev.ops, lo, hi):
        instr = s.name.split(" = ", 1)[0].lstrip("%")
        out[f"{s.program or '?'}:{instr}"] += s.seconds
    return dict(out)
