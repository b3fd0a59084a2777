"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
gaps and per-program device time, attributed to host spans.

What the reduction keys on, as the TPU runtime writes it:

* the device plane ``/device:TPU:<n>``: its line ``XLA Modules`` holds one
  event per program execution, named ``jit_<function>(<hash>)``; its line
  ``XLA Ops`` holds the HLO operations inside them (nested: a conditional
  contains its branch's operations).
* the host plane ``/host:CPU``: one line per thread. ``PjitFunction(<f>)``
  marks the launch of ``jit_<f>`` (written twice, nested, per launch);
  ``jax.profiler.TraceAnnotation`` spans show up under their own names.
* the device and host clocks in the file are offset by about a
  millisecond. The offset is estimated from the launches: the k-th launch
  of ``f`` on the host is paired with the k-th execution of ``jit_f`` on
  the device, and device times are shifted by the median gap so that an
  execution starts where its launch does.

A host span here is an annotation whose name is dotted lower-case words
(``bench.probe``, ``queue.flush``, ``store.fold``): the harness's own spans
and the program's ``obs`` spans. Idle gaps are labelled with the span that
overlaps them most, the innermost one on a tie. Device operations are
counted by self time (an operation's duration less that of the operations
nested in it).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
MODULE = re.compile(r"^jit_(.+?)\(\d+\)$")
WINDOW_SPAN = "bench.traced"     # the harness's span over the traced part
OP_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(\.\d+)*(\.clone)?\s*=")


class NoDevicePlane(ValueError):
    pass


@dataclass
class Event:
    name: str
    start: float              # ns
    end: float
    line: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """The parts of one trace the reduction reads (ns, one clock)."""
    modules: list              # Event per program execution on the device
    ops: list                  # Event per HLO op (line = owning module)
    spans: list                # host annotation spans, line = thread
    launches: list             # host PjitFunction launches, name = f
    window: tuple              # (start, end) of the traced window
    offset_ns: float = 0.0     # device minus host clock, removed


def _outermost(events: list) -> list:
    """Drop events nested in an event of the same name on the same line
    (the runtime writes each launch twice, one inside the other)."""
    out = []
    last_end = defaultdict(lambda: -np.inf)
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        key = (e.line, e.name)
        if e.start >= last_end[key]:
            out.append(e)
            last_end[key] = e.end
    return out


def _module_fn(name: str) -> str:
    m = MODULE.match(name)
    return m.group(1) if m else name


def clock_offset(modules: list, launches: list) -> float:
    """Median of (device start - host launch start) over launches paired
    with executions by function name and order; 0 if none pair up."""
    by_fn = defaultdict(list)
    for m in modules:
        by_fn[_module_fn(m.name)].append(m.start)
    host = defaultdict(list)
    for e in launches:
        host[e.name].append(e.start)
    diffs = []
    for fn, dev in by_fn.items():
        h = host.get(fn, [])
        if h and len(h) == len(dev):
            diffs.extend(np.sort(dev) - np.sort(h))
    return float(np.median(diffs)) if diffs else 0.0


def load(path: str, device_index: int = 0) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    dev = data.find_plane_with_name(f"/device:TPU:{device_index}")
    host = data.find_plane_with_name("/host:CPU")
    if dev is None:
        raise NoDevicePlane(f"{path}: no /device:TPU:{device_index} plane")
    modules, ops = [], []
    for ln in dev.lines:
        if ln.name == "XLA Modules":
            modules = [Event(e.name, e.start_ns, e.end_ns, "")
                       for e in ln.events]
        elif ln.name == "XLA Ops":
            ops = [Event(e.name, e.start_ns, e.end_ns, "")
                   for e in ln.events]
    spans, launches = [], []
    if host is not None:
        # every Python thread's line is named "python3": key lines by
        # their position, so spans and launches pair within one thread
        for i, ln in enumerate(host.lines):
            line = f"{ln.name}#{i}"
            for e in ln.events:
                name = e.name
                if SPAN_NAME.match(name):
                    spans.append(Event(name, e.start_ns, e.end_ns, line))
                else:
                    m = LAUNCH.match(name)
                    if m:
                        launches.append(Event(m.group(1), e.start_ns,
                                              e.end_ns, line))
    launches = _outermost(launches)
    window = _window(modules, spans)
    tr = Trace(modules=sorted(modules, key=lambda e: e.start),
               ops=sorted(ops, key=lambda e: e.start),
               spans=sorted(spans, key=lambda e: e.start),
               launches=sorted(launches, key=lambda e: e.start),
               window=window)
    _align(tr)
    _own_ops(tr)
    return tr


def _window(modules, spans) -> tuple:
    """The traced window on the trace's clock: the harness's
    ``bench.traced`` span, else the span of every event the trace holds
    (the profiler's own start and stop are on another clock)."""
    marks = [e for e in spans if e.name == WINDOW_SPAN]
    if marks:
        return (min(e.start for e in marks), max(e.end for e in marks))
    starts = [e.start for e in modules + spans]
    ends = [e.end for e in modules + spans]
    if not starts:
        return (0.0, 0.0)
    return (min(starts), max(ends))


def _align(tr: Trace) -> None:
    off = clock_offset(tr.modules, tr.launches)
    tr.offset_ns = off
    for e in tr.modules + tr.ops:
        e.start -= off
        e.end -= off


def _own_ops(tr: Trace) -> None:
    """Label each op with the module execution it ran in."""
    starts = [m.start for m in tr.modules]
    for op in tr.ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < tr.modules[i].end:
            op.line = tr.modules[i].name


def busy_intervals(modules: list) -> list:
    """Union of the program executions, as sorted disjoint (start, end)."""
    out = []
    for m in sorted(modules, key=lambda e: e.start):
        if out and m.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], m.end)
        else:
            out.append([m.start, m.end])
    return [tuple(x) for x in out]


def busy_ns(modules: list, window: tuple) -> float:
    lo, hi = window
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in busy_intervals(modules))


def idle_gaps(modules: list, window: tuple) -> list:
    """(start, end) of every stretch of the window with no program
    running on the device."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in busy_intervals(modules):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [g for g in gaps if g[1] > g[0]]


def label_gaps(gaps: list, spans: list) -> list:
    """[(label, gap ns)] for each gap: the host span overlapping it most,
    the innermost one on a tie; ``"no span"`` where none is open."""
    out = []
    order = sorted(spans, key=lambda e: e.start)
    i, active = 0, []
    for g0, g1 in gaps:
        while i < len(order) and order[i].start < g1:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > g0]
        best, best_key = "no span", None
        for s in active:
            ov = min(s.end, g1) - max(s.start, g0)
            key = (ov, -s.dur)
            if ov > 0 and (best_key is None or key > best_key):
                best, best_key = s.name, key
        out.append((best, g1 - g0))
    return out


def launch_categories(tr: Trace, prefix: str = "bench.") -> dict:
    """{function: {harness span: launches}}: the innermost harness span
    (name starting with ``prefix``) open on the launching thread."""
    by_line = defaultdict(list)
    for s in tr.spans:
        if s.name.startswith(prefix):
            by_line[s.line].append(s)
    out = defaultdict(lambda: defaultdict(int))
    for e in tr.launches:
        inner = None
        for s in by_line.get(e.line, ()):
            if s.start <= e.start and e.end <= s.end and \
                    (inner is None or s.dur < inner.dur):
                inner = s
        out[e.name][inner.name if inner else "none"] += 1
    return {k: dict(v) for k, v in out.items()}


def _shares(cats: dict, fn: str) -> dict:
    """{harness span: share} of a function's launches."""
    c = cats.get(fn)
    if not c:
        return {"unlaunched": 1.0}
    total = sum(c.values())
    return {cat: n / total for cat, n in c.items()}


def device_ns_by_category(tr: Trace, prefix: str = "bench.") -> dict:
    """Device time of the program executions, split over the harness
    span their launches came from (a function launched under several
    spans is split by launch count)."""
    cats = launch_categories(tr, prefix)
    out = defaultdict(float)
    lo, hi = tr.window
    for m in tr.modules:
        if m.end <= lo or m.start >= hi:
            continue
        for cat, share in _shares(cats, _module_fn(m.name)).items():
            out[cat] += m.dur * share
    return dict(out)


def op_short_name(op: Event) -> str:
    m = OP_NAME.match(op.name)
    inst = m.group(1) if m else op.name.split()[0]
    return f"{_module_fn(op.line)}:{inst}"


def op_self_ns(ops: list) -> list:
    """[(op, self ns)]: each op's duration less its nested ops'."""
    out = []
    stack = []                        # [op, child ns]
    for op in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and op.start >= stack[-1][0].end:
            done, child = stack.pop()
            out.append((done, max(0.0, done.dur - child)))
        if stack:
            stack[-1][1] += op.dur
        stack.append([op, 0.0])
    while stack:
        done, child = stack.pop()
        out.append((done, max(0.0, done.dur - child)))
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The contract's ``breakdown``: device ops with the most self time,
    and idle time by the host span open during it, in seconds."""
    ops = defaultdict(float)
    for op, ns in op_self_ns(tr.ops):
        ops[op_short_name(op)] += ns
    idle = defaultdict(float)
    spans = [s for s in tr.spans if s.name != WINDOW_SPAN]
    for label, ns in label_gaps(idle_gaps(tr.modules, tr.window), spans):
        idle[label] += ns
    rank = lambda d: [[k, v * 1e-9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}


@dataclass
class Reduction:
    """What the per-layer metric readers take from a trace."""
    window_s: float
    busy_s: float
    device_s_by_category: dict = field(default_factory=dict)
    kernel_s: dict = field(default_factory=dict)  # custom-call self s
    span_counts: dict = field(default_factory=dict)  # spans in window
    breakdown: dict = field(default_factory=dict)


def kernel_self_ns(tr: Trace, categories: dict, category: str) -> dict:
    """Self time of each Pallas kernel (``tpu_custom_call`` op) in the
    traced window, keyed by op name, counted for ``category`` by the
    share of its program's launches made from there (as device time is)."""
    lo, hi = tr.window
    out = defaultdict(float)
    for op, ns in op_self_ns(tr.ops):
        if 'custom_call_target="tpu_custom_call"' not in op.name or \
                op.end <= lo or op.start >= hi:
            continue
        share = _shares(categories, _module_fn(op.line)).get(category, 0.0)
        if share:
            out[op_short_name(op)] += ns * share
    return dict(out)


def reduce(path: str, category: str = "bench.probe"):
    """The Reduction of one trace, or None where it holds no TPU plane."""
    try:
        tr = load(path)
    except NoDevicePlane:
        return None
    lo, hi = tr.window
    cats = launch_categories(tr)
    counts = defaultdict(int)
    for e in tr.spans:
        if lo <= e.start < hi:
            counts[e.name] += 1
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns(tr.modules, tr.window) * 1e-9,
        device_s_by_category={k: v * 1e-9 for k, v in
                              device_ns_by_category(tr).items()},
        kernel_s={k: v * 1e-9 for k, v in
                  kernel_self_ns(tr, cats, category).items()},
        span_counts=dict(counts),
        breakdown=breakdown(tr))
