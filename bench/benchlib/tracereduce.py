"""From a profiler trace to the device's busy time, the time of each
program and kernel, and the idle gaps by what the host was doing.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(with nothing but JAX) into plain ``Event`` records; ``summarize`` is
pure and works on those records, so that it can be checked on a small
recorded trace.

* Device events are those of the TPU planes' ``XLA Ops`` line (one event
  per operation run on the chip, named by its HLO text); the ``XLA
  Modules`` line holds one event per run of a jitted program, and an op
  belongs to the program whose event contains it.
* Host events are the benchmark's own ``bench.*`` annotations around
  each submit burst, scheduler step and wait, and the events JAX itself
  records on the same thread inside them (``PjitFunction(add)``,
  ``DevicePut``, ...), which say what a step was doing.
* The traced window runs from the first host annotation's start to the
  last one's end; busy time is the union of device-op intervals inside
  it, averaged over the chips that ran anything.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
STEP = "bench.step"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    module: str = ""        # ops: the program (module line) it ran in

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def load_events(trace_dir) -> list:
    """Every device op and module event and every ``bench.*`` host
    annotation of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    pbs = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(pbs[-1]))
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [Event(plane.name, line.name, ev.name, int(ev.start_ns),
                         int(ev.duration_ns)) for ev in line.events]
            # a host thread counts when it ran the benchmark's loop
            if dev or any(e.name.startswith(HOST_PREFIX) for e in evs):
                out.extend(evs)
    return out


def module_name(name: str) -> str:
    """``jit_rule_search_fused(2607...)`` -> ``jit_rule_search_fused``."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """An op event's name is its HLO text; its short name is what comes
    before `` = ``."""
    return name.split(" = ", 1)[0]


def attribute_modules(ops, mods) -> None:
    """Set each op's ``module`` to the module event that contains it on
    the same chip (both sorted by start; one pass)."""
    by_plane = {}
    for m in mods:
        by_plane.setdefault(m.plane, []).append(m)
    for plane, ms in by_plane.items():
        ms.sort(key=lambda e: e.start_ns)
        j = 0
        for o in sorted((o for o in ops if o.plane == plane),
                        key=lambda e: e.start_ns):
            while j < len(ms) and ms[j].end_ns <= o.start_ns:
                j += 1
            if j < len(ms) and ms[j].start_ns <= o.start_ns:
                o.module = module_name(ms[j].name)


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> int:
    """Total length of the intersection of two merged, sorted interval
    lists (one two-pointer pass)."""
    i = j = 0
    out = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def intersect(a, b) -> list:
    """The intersection of two merged, sorted interval lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo, hi) -> list:
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: list          # device Events inside the window
    modules: list      # module Events inside the window
    op_seconds: dict   # "program:op" -> seconds
    idle_by_host: dict  # host activity -> idle seconds under it
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def matching(self, pattern: str, line: str = OPS_LINE,
                 module: str = "") -> list:
        """Events of ``line`` whose name matches ``pattern`` (and, for
        ops, whose program matches ``module``)."""
        rx, mx = re.compile(pattern), re.compile(module)
        if line == OPS_LINE:
            return [e for e in self.ops
                    if rx.search(e.name) and mx.search(e.module)]
        return [e for e in self.modules if rx.search(e.name)]

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle[:10]]}


def summarize(events) -> Summary:
    host = [e for e in events if e.name.startswith(HOST_PREFIX)]
    loop = {(e.plane, e.line) for e in host}
    inner = [e for e in events if (e.plane, e.line) in loop
             and not e.name.startswith(HOST_PREFIX)]
    if not host:
        raise ValueError("the trace holds no bench.* host annotation")
    lo = min(e.start_ns for e in host)
    hi = max(e.end_ns for e in host)
    ops = [e for e in events if e.line == OPS_LINE
           and e.plane.startswith(DEVICE_PREFIX)
           and e.end_ns > lo and e.start_ns < hi]
    mods = [e for e in events if e.line == MODULES_LINE
            and e.plane.startswith(DEVICE_PREFIX)
            and e.end_ns > lo and e.start_ns < hi]
    attribute_modules(ops, mods)
    planes = sorted({e.plane for e in ops})
    busy_ns = 0
    busy_iv = []
    for p in planes:
        iv = union(clip([(e.start_ns, e.end_ns) for e in ops
                         if e.plane == p], lo, hi))
        busy_ns += total(iv)
        if not busy_iv:
            busy_iv = iv      # idle gaps are read on the first chip
    chips = max(len(planes), 1)
    op_s = {}
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        key = f"{e.module}:{op_name(e.name)}"
        op_s[key] = op_s.get(key, 0.0) + (t - s) / 1e9 / chips
    idle = gaps(busy_iv, lo, hi)
    host_iv = {}
    for e in host:
        host_iv.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    by_host = {}
    covered = []
    for name, iv in host_iv.items():
        iv = union(iv)
        covered.extend(iv)
        if name != STEP:
            by_host[name] = overlap(iv, idle) / 1e9
    rest = total(idle) - overlap(union(covered), idle)
    if rest > 0:
        by_host["between annotations"] = rest / 1e9
    # inside steps: by the JAX host call the step was in (nested calls
    # overlap), the rest is the program's own Python
    in_step = intersect(union(host_iv.get(STEP, [])), idle)
    inner_iv = {}
    for e in inner:
        inner_iv.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    for name, iv in inner_iv.items():
        ov = overlap(union(iv), in_step)
        if ov:
            by_host[f"{STEP}/{name}"] = ov / 1e9
    py = total(in_step) - overlap(
        union([iv for ivs in inner_iv.values() for iv in ivs]), in_step)
    if py > 0:
        by_host[f"{STEP}/python"] = py / 1e9
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9 / chips,
                   ops=ops, modules=mods, op_seconds=op_s,
                   idle_by_host=by_host, chips=chips)
