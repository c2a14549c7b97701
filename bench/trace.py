"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, time per operation name and per
program, and the device's idle gaps, each named by the harness span
(``bench.*``) that the host was in during it.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run, named by its HLO text
(``%flash_decode_paged.3 = (...) custom-call(...)``), and their ``XLA
Modules`` line one per program run (``jit__decode_fn(<fingerprint>)``).
An operation is counted under its instruction name without the ``%`` and
the numeric suffix (``flash_decode_paged``). Host spans are the events of
the ``/host:CPU`` plane. All times are on the profiler's one clock, in
nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Reduced:
    window_s: float                          # traced window's length
    busy_s: float                            # device busy, mean over chips
    op_s: Dict[str, float]                   # op name -> seconds
    op_n: Dict[str, int]                     # op name -> events
    module_s: Dict[str, float]               # program name -> seconds
    module_n: Dict[str, int]                 # program name -> runs
    idle_by_span: Dict[str, float]           # host span -> idle seconds
    n_devices: int

    def ops_matching(self, part: str) -> Tuple[float, int]:
        """Seconds and events of the operations whose name (``op_name``)
        holds ``part``."""
        s = sum(v for k, v in self.op_s.items() if part in k)
        n = sum(v for k, v in self.op_n.items() if part in k)
        return s, n

    def modules_matching(self, part: str) -> Tuple[float, int]:
        s = sum(v for k, v in self.module_s.items() if part in k)
        n = sum(v for k, v in self.module_n.items() if part in k)
        return s, n

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%flash_decode_paged.3 = (...) custom-call(...)`` ->
    ``flash_decode_paged``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _span_at(spans, a, b) -> str:
    """The innermost harness span that covers most of ``[a, b)``."""
    best, best_key = "outside harness spans", (0.0, 0.0)
    for name, s0, s1 in spans:
        ov = min(b, s1) - max(a, s0)
        if ov <= 0:
            continue
        key = (ov, -(s1 - s0))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_planes(planes, window: Optional[Tuple[float, float]] = None
                  ) -> Reduced:
    """Reduce profiler planes (``ProfileData.planes``, or any objects with
    ``name``, ``lines``; lines with ``name``, ``events``; events with
    ``name``, ``start_ns``, ``duration_ns``). The window is the harness's
    ``bench.traced`` span unless given."""
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(op_name(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            if ops:
                devices.append((ops, mods))
    if window is None:
        marks = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
        if marks:
            window = (min(a for a, _ in marks), max(b for _, b in marks))
        else:
            allops = [(s, s + d) for ops, _ in devices for _, s, d in ops]
            window = (min(a for a, _ in allops), max(b for _, b in allops))
    lo, hi = window
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    for ops, mods in devices:
        live = [(n, s, d) for n, s, d in ops if s < hi and s + d > lo]
        for n, s, d in live:
            op_s[n] += d * 1e-9
            op_n[n] += 1
        for n, s, d in mods:
            if s < hi and s + d > lo:
                mod_s[n] += d * 1e-9
                mod_n[n] += 1
        busy = _merge(_clip([(s, s + d) for _, s, d in live], lo, hi))
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_span_at(inner, a, b)] += (b - a) * 1e-9
    n = max(len(devices), 1)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                   op_s=dict(op_s), op_n=dict(op_n), module_s=dict(mod_s),
                   module_n=dict(mod_n),
                   idle_by_span={k: v / n for k, v in idle.items()},
                   n_devices=len(devices))


def reduce_file(path: str, window=None) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window)
