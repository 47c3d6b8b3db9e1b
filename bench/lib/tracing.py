"""Profiler capture and the reduction from a trace to numbers.

A traced run records a steady stretch of the measured window with
`jax.profiler`. The reduction reads the `.xplane.pb` with
`jax.profiler.ProfileData`: device operations (the "XLA Ops" line of each
`/device:TPU:<n>` plane), the benchmark's host spans (TraceAnnotations
named `bench.*`, and the engine's own spans: `serve.*`,
`admission.*`, `dispatch.*`, `router.*`, `state.*`), and from them the
busy time, the idle gaps named by the host span open across them, and
the operations that took most time.
"""
from __future__ import annotations

import glob
import os
import re
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Tuple

_SPANS_ON = False
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_SPAN = re.compile(r"^(bench|serve|admission|dispatch|router|state)\.")


def host_memory() -> str:
    """This process's peak resident set and the memory the machine has
    left (Linux)."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    avail = "unknown"
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    avail = f"{int(line.split()[1]) * 1024 / 1e9:.1f} GB"
    except OSError:
        pass
    return f"host peak RSS {peak / 1e9:.1f} GB, available {avail}"


@contextmanager
def phase(log, name: str):
    """Log how long a phase of set-up or checking took, and the host's
    memory after it (stderr)."""
    t0 = time.perf_counter()
    yield
    log(f"phase {name}: {time.perf_counter() - t0:.2f} s; {host_memory()}")


def span(name: str):
    """A host span in the profiler's trace while a trace is being taken;
    nothing otherwise."""
    if not _SPANS_ON:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Takes `length` seconds of trace starting `start_at` seconds into
    the window; the run loop calls poll() with the elapsed time."""

    def __init__(self, logdir: str, start_at: float, length: float):
        self.logdir, self.start_at, self.length = logdir, start_at, length
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def poll(self, elapsed: float):
        global _SPANS_ON
        import jax
        if self.t_start is None and elapsed >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            _SPANS_ON = True
            self.t_start = time.perf_counter()
        elif (self.t_start is not None and self.t_stop is None
              and elapsed >= self.start_at + self.length):
            self.stop()

    def stop(self):
        global _SPANS_ON
        import jax
        if self.t_start is not None and self.t_stop is None:
            _SPANS_ON = False
            jax.profiler.stop_trace()
            self.t_stop = time.perf_counter()


class Trace:
    """Device operations and host spans of one trace, in ns on the
    trace's own clock. The traced window is the stretch from the first
    to the last device op or host span: starting and stopping the
    profiler takes host time that belongs to neither."""

    def __init__(self, ops: Dict[int, List[Tuple]], spans: List[Tuple],
                 window_s: float):
        self.ops = ops            # device -> [(start, end, name, module)]
        self.spans = spans        # [(start, end, name)]
        self.window_s = window_s

    # -- loading ----------------------------------------------------------------
    @classmethod
    def load(cls, logdir: str) -> "Trace":
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no trace under {logdir}")
        return cls.from_profile(ProfileData.from_file(paths[-1]))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops: Dict[int, List[Tuple]] = {}
        mods: Dict[int, List[Tuple]] = {}
        spans: List[Tuple] = []
        cpu_ops: List[Tuple] = []
        for plane in pd.planes:
            m = _DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name in ("XLA Ops", "XLA Modules"):
                    dest = ops if line.name == "XLA Ops" else mods
                    dest.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
                elif plane.name.startswith("/host:"):
                    for e in line.events:
                        if _HOST_SPAN.match(e.name):
                            spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns, e.name))
                        elif plane.name == "/host:CPU" and e.duration_ns:
                            st = dict(e.stats)
                            if "hlo_module" in st:
                                cpu_ops.append((e.start_ns, e.start_ns
                                                + e.duration_ns, e.name,
                                                str(st["hlo_module"])))
        for dev, v in ops.items():
            ops[dev] = _with_modules(sorted(v), sorted(mods.get(dev, [])))
        if not ops and cpu_ops:
            ops[0] = sorted(cpu_ops)  # a CPU rehearsal: its ops stand in
        spans.sort(key=lambda x: x[0])
        pts = [x for v in ops.values() for x in (v[0][0], v[-1][1]) if v]
        pts += [x for s in spans for x in s[:2]]
        window_s = (max(pts) - min(pts)) / 1e9 if pts else 0.0
        return cls(ops, spans, window_s)

    # -- reductions --------------------------------------------------------------
    @staticmethod
    def _union(intervals):
        out = []
        for s, e in sorted(intervals):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = [sum(e - s for s, e in self._union((o[0], o[1]) for o in v))
               for v in self.ops.values()]
        return sum(tot) / len(tot) / 1e9

    def op_seconds(self, match) -> Tuple[float, int]:
        """Total device seconds and count of the operations for which
        match(name, module) is true, averaged over the devices."""
        if not self.ops:
            return 0.0, 0
        secs, cnt = [], []
        for v in self.ops.values():
            hit = [(e - s) for s, e, n, mod in v if match(n, mod)]
            secs.append(sum(hit) / 1e9)
            cnt.append(len(hit))
        return sum(secs) / len(secs), round(sum(cnt) / len(cnt))

    def top_ops(self, k: int = 10) -> List[List]:
        """The device operations that took most time, by name, on
        device 0 (or the lowest-numbered device)."""
        if not self.ops:
            return []
        dev = min(self.ops)
        acc: Dict[str, float] = {}
        for s, e, n, mod in self.ops[dev]:
            key = op_label(n, mod)
            acc[key] = acc.get(key, 0.0) + (e - s) / 1e9
        return [[n, v] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10, min_ns: int = 1000) -> List[List]:
        """Idle time on the first device, by the innermost host span open
        at each gap's middle ("none" when no span was open), longest
        total first."""
        if not self.ops:
            return []
        busy = self._union((o[0], o[1]) for o in self.ops[min(self.ops)])
        acc: Dict[str, float] = {}
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            if s1 - e0 < min_ns:
                continue
            mid = (e0 + s1) / 2
            name, best = "none", None
            for s, e, n in self.spans:
                if s > mid:
                    break
                if e >= mid and (best is None or s >= best):
                    name, best = n, s
            acc[name] = acc.get(name, 0.0) + (s1 - e0) / 1e9
        return [[n, v] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def _with_modules(ops, mods):
    """Attach to each op the name of the program (XLA module) whose run
    contains it, without the module's fingerprint."""
    out, j = [], 0
    for s, e, n in ops:
        while j < len(mods) and mods[j][1] < s:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else ""
        out.append((s, e, n, mod.split("(")[0]))
    return out


_HLO = re.compile(r"^%(\S+) = (.+?) ([a-zA-Z][\w\-]*)\(")


def op_label(name: str, module: str) -> str:
    """A readable label of a device operation: its program, and from the
    HLO text the op's result shape and opcode (layouts dropped)."""
    m = _HLO.match(name)
    if m:
        shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
        name = f"{m.group(1).split('.')[0]} {shape} {m.group(3)}"
    return f"{module}: {name[:140]}" if module else name[:140]
