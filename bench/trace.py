"""Reading a profiler trace into device op intervals and host spans.

A run with ``--trace 1`` records one stretch of its window with the JAX
profiler.  :func:`load` turns the ``.xplane.pb`` it writes into a
:class:`Trace`: per device, the op and module events (name, start, end in
seconds on the host's clock), and the benchmark loop's own host spans.  The
per-layer readers under ``bench/metrics/`` and the ``breakdown`` of the
result line are computed from it by the functions below.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import statistics
from typing import Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]          # (name, start s, end s)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans the benchmark's loop writes; idle gaps are named after them
HOST_SPANS = ("batch_wait", "dispatch", "fetch_metrics")
WINDOW_SPAN = "traced_window"


@dataclasses.dataclass
class Device:
    ops: List[Interval]
    modules: List[Interval]
    # this device's whole traced steps and their window (see trim_to_steps);
    # until then the traced stretch, with no step counted
    window: Tuple[float, float] = (0.0, 0.0)
    step_events: List[Interval] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def steps(self) -> int:
        return len(self.step_events)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Interval]                  # the loop's spans
    window: Tuple[float, float]           # the traced stretch of the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def from_profile(pdata) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices, host, window = {}, [], None
    for plane in pdata.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: mods}.get(line.name)
                if dst is not None:
                    dst.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
            devices[int(m.group(2))] = Device(ops, mods)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    host.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                elif e.name == WINDOW_SPAN:
                    window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    for dev in devices.values():
        dev.window = window
    return Trace([devices[k] for k in sorted(devices)], sorted(host, key=lambda s: s[1]),
                 window)


def load(directory: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {directory}, found {files}")
    return from_profile(ProfileData.from_file(files[0]))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    out = []
    for name, a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged (start, end) spans covered by any interval."""
    spans = sorted((a, b) for _, a, b in intervals)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(dev: Device, window: Tuple[float, float]) -> float:
    """Seconds of ``window`` in which some op ran on ``dev``."""
    return sum(b - a for a, b in union(clip(dev.ops, *window)))


def idle_gaps(dev: Device, window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The stretches of ``window`` in which no op ran on ``dev``."""
    lo, hi = window
    gaps, t = [], lo
    for a, b in union(clip(dev.ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_seconds(dev: Device, window: Tuple[float, float],
               kinds: Optional[Tuple[str, ...]] = None) -> float:
    """Device seconds of the ops in ``window`` whose HLO opcode (the name
    up to its ``.N`` suffix, ``-start``/``-done`` parts included) starts with
    one of ``kinds``; all ops when ``kinds`` is None."""
    return sum(b - a for name, a, b in clip(dev.ops, *window)
               if kinds is None or opcode(name).startswith(kinds))


_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9_-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an op event.  TPU traces name an op by its HLO text,
    ``%all-to-all.3 = bf16[...] all-to-all(...)``: the word before the first
    operand list; a bare name loses its ``.N`` suffix."""
    m = _OPCODE.search(name)
    return m.group(1) if m else re.sub(r"\.\d+$", "", name)


def short_name(name: str) -> str:
    """``%fusion.52 = (...) fusion(...)`` -> ``fusion.52``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_module(name: str, module: str) -> bool:
    """Whether a module event's name is ``module`` (JAX appends ``(<id>)``)."""
    return name == module or name.startswith(module + "(")


def trim_to_steps(tr: Trace, module: str) -> None:
    """Give each device a window of its own whole steps, from the start of
    one ``module`` event to the start of a later one, so that each step
    counted brings its own idle tail.  Of the events inside the traced
    stretch, the first is left out, and so is any under half the median's
    length: the profiler may cut events short where it starts and stops.
    Each device's clock is its own, so device 0's window would cut another
    device's steps.  A device with fewer than three such events keeps the
    traced stretch and counts no step."""
    lo, hi = tr.window
    for dev in tr.devices:
        ev = [e for e in dev.modules
              if is_module(e[0], module) and e[1] > lo and e[2] < hi]
        if not ev:
            continue
        half = statistics.median(b - a for _, a, b in ev) / 2
        ev = sorted((e for e in ev if e[2] - e[1] >= half), key=lambda e: e[1])[1:]
        if len(ev) >= 2:
            dev.window, dev.step_events = (ev[0][1], ev[-1][1]), ev[:-1]


def traced_steps_per_s(tr: Trace) -> Optional[float]:
    """Whole steps per second of the trace, mean over the devices that
    counted steps; None where none did."""
    rates = [d.steps / d.window_s for d in tr.devices if d.steps]
    return sum(rates) / len(rates) if rates else None


def host_span_at(host: List[Interval], t: float) -> str:
    """Name of the loop span the host was in at time ``t``."""
    for name, a, b in host:
        if a <= t <= b:
            return name
    return "outside_loop_spans"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """Device ops that took most time (seconds per device window, mean over
    devices) and the longest idle gaps of device 0, each named by the host
    span it fell in."""
    totals: dict = {}
    for dev in tr.devices:
        for name, a, b in clip(dev.ops, *dev.window):
            name = short_name(name)
            totals[name] = totals.get(name, 0.0) + (b - a)
    n = max(len(tr.devices), 1)
    ops = sorted(((k, v / n) for k, v in totals.items()), key=lambda kv: -kv[1])
    gaps = []
    if tr.devices:
        for a, b in idle_gaps(tr.devices[0], tr.devices[0].window):
            gaps.append((host_span_at(tr.host, (a + b) / 2), b - a))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def collective_ms(tr: Trace, kinds: Tuple[str, ...]) -> Optional[float]:
    """Milliseconds per step of the ops of ``kinds``, each device over its
    own whole steps, mean over the devices; None where no such op ran or no
    step was traced."""
    per_dev = [op_seconds(d, d.window, kinds) / d.steps
               for d in tr.devices if d.steps]
    if not any(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev)


def busy_share(tr: Trace) -> Optional[float]:
    """Share of each device's window in which some op ran, mean over the
    devices."""
    shares = [busy_s(d, d.window) / d.window_s for d in tr.devices if d.window_s > 0]
    return sum(shares) / len(shares) if shares else None
