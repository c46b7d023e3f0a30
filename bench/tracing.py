"""The profiler trace of a traced run, and its reduction to numbers.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps three kinds of event, as ``[start_ns, duration_ns, name]`` on the
   profiler's one clock: each device's operations (line ``XLA Ops``), each
   device's program runs (line ``XLA Modules``), and every host event of
   the thread that ran the benchmark's ``bench.*`` spans (those spans, the
   program's ``store.*``/``router.*`` spans, and jax's own dispatch,
   transfer and wait events).
2. :func:`reduce` turns those into busy and idle time over the traced
   window (the host span ``bench.window``), the self time of each device
   operation (its time less that of the operations inside it, such as a
   loop's body inside the loop), the time and runs of each program, and
   the idle gaps named by the innermost host event open at their middle.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

BENCH_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off: it would put a cost
    on every Python call of the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def extract(log_dir: str) -> dict:
    """``{"devices": {id: {"ops": [...], "modules": [...]}}, "host": [...]}``
    from the one ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [[float(e.start_ns), float(e.duration_ns), e.name]
                      for e in line.events]
            if m:
                kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if kind:
                    devices.setdefault(m.group(1), {"ops": [], "modules": []})[kind] += events
            elif plane.name.startswith("/host:") and any(
                    e[2].startswith(BENCH_PREFIX) for e in events):
                host += events
    return {"devices": devices, "host": host}


_HLO = re.compile(r"^%?([^ ]+) = .*?\s([a-z][a-z0-9_-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def op_name(program: str, hlo: str) -> str:
    """``program/instruction opcode [fusion kind]`` from an op's HLO text."""
    m = _HLO.match(hlo)
    if not m:
        return f"{program}/{hlo[:60]}"
    k = _KIND.search(hlo)
    return f"{program}/{m.group(1)} {m.group(2)}" + (f" {k.group(1)}" if k else "")


def _self_times(ops, modules):
    """(start, end, name) of each op with its self time: its duration less
    that of the ops nested in it; ops are named by their program."""
    mods = sorted((s, s + d, n.split("(")[0]) for s, d, n in modules)
    out, stack, mi = [], [], 0
    for s, d, hlo in sorted(ops, key=lambda e: (e[0], -e[1])):
        while mi + 1 < len(mods) and mods[mi + 1][0] <= s:
            mi += 1
        prog = mods[mi][2] if mods and mods[mi][0] <= s < mods[mi][1] else "no program"
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, s + d, op_name(prog, hlo), d]
        if stack:
            stack[-1][3] -= min(s + d, stack[-1][1]) - s
        stack.append(rec)
        out.append(rec)
    return out


def _clip(events, lo, hi):
    for s, d, name in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b, name


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class _Spans:
    """Host spans sorted by start, for finding the innermost open at t."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((d for _, d, _ in self.spans), default=0.0)

    def innermost(self, t: float) -> str:
        best = None
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] >= t - self.longest:
            s, d, name = self.spans[i]
            if t <= s + d and (best is None or d < best[0]):
                best = (d, name)
            i -= 1
        return best[1] if best else "no host event"


def reduce(events: dict) -> dict:
    """Busy and idle seconds per device over the traced window, self time
    per device operation and time per program (summed over devices),
    program runs, and idle time by the host event open in each gap.  An
    operation counts where it lies wholly inside the window."""
    windows = [e for e in events["host"] if e[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, dur, _ = windows[0]
    hi = lo + dur
    spans = _Spans(e for e in events["host"] if e[2] != WINDOW_SPAN)
    busy, op_s, prog_s, prog_n = {}, defaultdict(float), defaultdict(float), defaultdict(int)
    gaps = defaultdict(float)
    for dev, ev in sorted(events["devices"].items()):
        ops = list(_clip(ev["ops"], lo, hi))
        for s, e, name, self_ns in _self_times(ev["ops"], ev["modules"]):
            if lo <= s and e <= hi:
                op_s[name] += self_ns / 1e9
        for a, b, name in _clip(ev["modules"], lo, hi):
            prog = name.split("(")[0]
            prog_s[prog] += (b - a) / 1e9
            prog_n[prog] += 1
        merged = _union((a, b) for a, b, _ in ops)
        busy[dev] = sum(b - a for a, b in merged) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[spans.innermost((a + b) / 2)] += (b - a) / 1e9
    return {"window_s": dur / 1e9, "busy_s": busy, "op_s": dict(op_s),
            "program_s": dict(prog_s), "program_runs": dict(prog_n),
            "idle_s": dict(gaps)}


def breakdown(red: dict, top: int = 10) -> dict:
    """The traced run's ``breakdown``: the device operations with the most
    self time, and idle time by the host event open in it, each summed
    over the devices, in seconds."""
    def best(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": best(red["op_s"]), "idle_gaps": best(red["idle_s"])}
