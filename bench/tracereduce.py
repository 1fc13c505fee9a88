"""Reduce a JAX profiler trace to device metrics.

Reads an ``.xplane.pb`` through ``jax.profiler.ProfileData`` (or any object
with the same ``planes -> lines -> events`` shape). A device plane is one
named ``/device:<KIND>:<n>`` (CPU excluded); its ops are the events of its
``XLA Ops`` line, its programs those of its ``XLA Modules`` line. The
measured window is the host annotation ``bench.window``; every interval is
clipped to it.

Busy time is the union of a device's op intervals, so overlapping ops count
once. Idle gaps are the holes in that union; each is named after what the
host was doing at its midpoint: the innermost ``bench.*`` annotation of the
harness there, then the longest other host event that covers it (any
thread, e.g. ``PjitFunction(_proj)``), or ``none``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:([A-Z_]+):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"ppermute|psum|send|recv", re.I)


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[int, int, str]]       # (start_ns, end_ns, name)
    modules: List[Tuple[int, int, str]]


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]               # (start_ns, end_ns)
    devices: List[Device]
    host_spans: List[Tuple[int, int, str]]  # bench.* annotations
    host_events: List[Tuple[int, int, str]] = dataclasses.field(
        default_factory=list)               # every other host event


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


_HLO = re.compile(r"^(%\S+) = .*? ([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.30 fusion`` for a device op named by its whole HLO text."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _events(line, names=lambda n: n) -> List[Tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), names(e.name))
            for e in line.events]


def parse(profile) -> Trace:
    """Pull the window, the device timelines and the harness's host
    annotations out of a ``ProfileData``."""
    devices, host, other, window = [], [], [], None
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m and m.group(1) != "CPU":
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line, short_name)
                elif line.name == MODULES_LINE:
                    mods = _events(line)
            devices.append(Device(plane.name, ops, mods))
            continue
        for line in plane.lines:
            for ev in _events(line):
                if ev[2] == WINDOW:
                    window = ev[:2] if window is None else \
                        (min(window[0], ev[0]), max(window[1], ev[1]))
                elif ev[2].startswith("bench."):
                    host.append(ev)
                elif ev[1] > ev[0]:
                    other.append(ev)
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW!r} annotation")
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    devices.sort(key=lambda d: int(_DEVICE.match(d.name).group(2)))
    return Trace(window, devices, sorted(host), other)


def _clip(ivs, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in ivs
            if e > lo and s < hi]


def union(ivs) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e, *_ in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device, window) -> int:
    return sum(e - s for s, e in union(_clip(dev.ops, window)))


def gaps(dev: Device, window) -> List[Tuple[int, int]]:
    """Idle intervals of one device inside the window."""
    lo, hi = window
    out, t = [], lo
    for s, e in union(_clip(dev.ops, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _host_at(tr: Trace, t: int) -> str:
    """What the host was doing at ``t``: the innermost (latest-starting)
    harness annotation covering it, and the longest other host event
    covering it."""
    best = None
    for s, e, name in tr.host_spans:
        if s > t:
            break
        if e >= t:
            best = name
    over = [(e - s, name) for s, e, name in tr.host_events if s <= t <= e]
    inner = max(over)[1] if over else "none"
    return f"host:{best or 'none'}/{inner}"


def summarize(tr: Trace, top: int = 10) -> Dict:
    """busy_s / window_s (means over devices), the top device ops by
    summed time (mean per device), the longest idle gaps of device 0 named
    by the host annotation over them, and per-device collective time."""
    window = tr.window
    n = len(tr.devices)
    busy = [busy_ns(d, window) for d in tr.devices]
    per_op: Dict[str, float] = defaultdict(float)
    coll = []
    for d in tr.devices:
        c = 0
        for s, e, name in _clip(d.ops, window):
            per_op[name] += (e - s) / n
            if COLLECTIVE.search(name):
                c += e - s
        coll.append(c)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    g = sorted(gaps(tr.devices[0], window), key=lambda iv: iv[0] - iv[1])
    idle = [[_host_at(tr, (s + e) // 2), (e - s) * 1e-9]
            for s, e in g[:top]]
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "busy_s_per_device": [b * 1e-9 for b in busy],
        "collective_s": sum(coll) / n * 1e-9,
        "n_devices": n,
        "n_ops": sum(len(d.ops) for d in tr.devices),
        "device_ops": [[name, s * 1e-9] for name, s in top_ops],
        "idle_gaps": idle,
    }


def module_seconds(tr: Trace, pattern: str) -> Optional[float]:
    """Summed device time of the programs whose name matches ``pattern``
    (mean per device); None where no such program ran."""
    rx = re.compile(pattern)
    tot, hit = 0, False
    for d in tr.devices:
        for s, e, name in _clip(d.modules, tr.window):
            if rx.search(name):
                tot += e - s
                hit = True
    return tot / len(tr.devices) * 1e-9 if hit else None


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return parse(ProfileData.from_file(find_xplane(log_dir)))
