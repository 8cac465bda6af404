"""From a ``jax.profiler`` trace to the numbers the per-layer readers use.

The trace is read with ``jax.profiler.ProfileData`` (nothing outside JAX):

* device planes are those named ``/device:TPU:<n>``; on each, the
  ``XLA Modules`` line holds one event per program execution and the
  ``XLA Ops`` line one per operation;
* the host plane ``/host:CPU`` holds, on the line of the thread that ran
  the window, its trace annotations (the program's ``repro.obs`` spans
  under ``obs.enable(jax_profiler=True)`` and the benchmark's own), on the
  same clock as the device.

The window is the benchmark's ``bench.window`` annotation. Within it:

* ``busy_s`` — the length of the union of the device's operation intervals
  (program intervals where a plane has no operation line), averaged over
  the device planes;
* ``programs`` — device seconds per XLA program, summed over executions
  (the name without its ``jit_`` prefix and ``(<id>)`` suffix);
* ``idle_gaps`` — the device's idle intervals, each put down to the
  innermost host annotation that covers its midpoint, summed by name.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]            # (start_ns, end_ns)
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW = "bench.window"


def union_length(intervals: Iterable[Interval], lo: float,
                 hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def program_name(event_name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def per_program(events: Iterable[Event], lo: float,
                hi: float) -> Dict[str, float]:
    """Device seconds per program, the executions clipped to the window."""
    out: Dict[str, float] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = program_name(name)
            out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def attribute_gaps(idle: List[Interval], host: List[Event]
                   ) -> Dict[str, float]:
    """Idle seconds by the innermost host annotation covering each gap's
    midpoint (``untraced host`` where none does)."""
    out: Dict[str, float] = {}
    evs = sorted((ev for ev in host if ev[0] != WINDOW),
                 key=lambda ev: ev[1])
    active: List[Event] = []
    i = 0
    for s, e in sorted(idle):
        mid = 0.5 * (s + e)
        while i < len(evs) and evs[i][1] <= mid:
            active.append(evs[i])
            i += 1
        active = [ev for ev in active if ev[2] >= mid]
        best: Optional[Event] = min(active, key=lambda ev: ev[2] - ev[1],
                                    default=None)
        key = best[0] if best else "untraced host"
        out[key] = out.get(key, 0.0) + (e - s) * 1e-9
    return out


def load(path: Path) -> Dict[str, object]:
    """Device and host events of the ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(str(files[-1]))
    devices: List[Dict[str, List[Event]]] = []
    host: List[Event] = []
    structure: Dict[str, List[str]] = {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        structure[plane.name] = sorted(lines)[:16]
        if DEVICE_PLANE.match(plane.name):
            devices.append({key: [(ev.name, ev.start_ns, ev.end_ns)
                                  for ev in lines[key].events]
                            for key in (MODULES, OPS) if key in lines})
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:       # the thread that ran the window
                evs = [(ev.name, ev.start_ns, ev.end_ns) for ev in ln.events
                       if ev.end_ns > ev.start_ns]
                if any(ev[0] == WINDOW for ev in evs):
                    host.extend(evs)
    return {"devices": devices, "host": host, "structure": structure}


def reduce(loaded: Dict[str, object]) -> Dict[str, object]:
    """The window's busy time, per-program device time and idle gaps."""
    host: List[Event] = loaded["host"]
    wins = [ev for ev in host if ev[0] == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    _, lo, hi = max(wins, key=lambda ev: ev[2] - ev[1])
    devices = loaded["devices"]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy = []
    programs: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for dev in devices:
        mods = dev.get(MODULES, [])
        ops = dev.get(OPS) or mods
        iv = [(s, e) for _, s, e in ops]
        busy.append(union_length(iv, lo, hi) * 1e-9)
        for k, v in per_program(mods, lo, hi).items():
            programs[k] = programs.get(k, 0.0) + v / len(devices)
        for k, v in attribute_gaps(gaps(iv, lo, hi), host).items():
            idle_by[k] = idle_by.get(k, 0.0) + v / len(devices)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy),
            "programs": programs, "idle_by_host": idle_by}


def breakdown(reduced: Dict[str, object], top: int = 10
              ) -> Dict[str, List[List[object]]]:
    """The programs that took most device time and the host work behind
    the longest idle stretches, ``top`` of each."""
    def ranked(d: Dict[str, float]) -> List[List[object]]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": ranked(reduced["programs"]),
            "idle_gaps": ranked(reduced["idle_by_host"])}
