"""Reduction of a profiler trace to the benchmark's device numbers.

Input is a JAX profiler trace (``.xplane.pb``) of one measured window, or,
in the tests, the same structure built by hand: per chip, the events of its
``XLA Ops`` line (every operation that ran on the device) and of its
``XLA Modules`` line (one event per run of a compiled program), and the
host's annotation events (the harness's ``window``, ``call`` and ``fetch``
spans), all on the trace's one clock in nanoseconds.

From it come, per chip: the busy time (the union of the operations'
intervals inside the window), the idle gaps, and the device time of each
program by name; and over the chips: the operations that took most time
and the longest idle gaps, each labelled by the host span it fell in.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
HOST_SPANS = ("call", "fetch")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclass
class ChipTrace:
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    chips: list              # ChipTrace, in device order
    host: list               # Event: the harness's host annotations
    window: tuple            # (start_ns, end_ns) of the measured window


def clip(intervals, lo: int, hi: int) -> list:
    """The ``(start, end)`` intervals cut to ``[lo, hi)``, empty ones
    dropped, sorted by start."""
    out = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sorted((s, e) for s, e in out if e > s)


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of the intervals inside ``[lo, hi)``: overlapping
    and nested operations count once."""
    total, reach = 0, lo
    for s, e in clip(intervals, lo, hi):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def idle_gaps(intervals, lo: int, hi: int) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi)`` that no interval
    covers, in time order."""
    gaps, reach = [], lo
    for s, e in clip(intervals, lo, hi):
        if s > reach:
            gaps.append((reach, s))
        reach = max(reach, e)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def program_name(event_name: str) -> str:
    """A program's name without the run id that the trace appends to a
    module event: ``jit__fused_sort_packed(123)`` -> ``jit__fused_sort_packed``."""
    return event_name.split("(", 1)[0].strip()


def module_ns(chip: ChipTrace, names, lo: int, hi: int) -> int:
    """Device nanoseconds, inside ``[lo, hi)``, of the runs of the programs
    named in ``names``."""
    names = set(names)
    return sum(e - s for s, e in clip(
        [(ev.start_ns, ev.end_ns) for ev in chip.modules
         if program_name(ev.name) in names], lo, hi))


def program_seconds(trace: Trace, names) -> float:
    """Device seconds of the named programs inside the window, summed over
    the chips."""
    lo, hi = trace.window
    return sum(module_ns(c, names, lo, hi) for c in trace.chips) / 1e9


def busy_ns(chip: ChipTrace, lo: int, hi: int) -> int:
    return union_ns([(ev.start_ns, ev.end_ns) for ev in chip.ops], lo, hi)


def op_name(event_name: str) -> str:
    """An operation's HLO name without its text: ``%fusion.3 = u32[...] ...``
    -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _labelled_ops(chip: ChipTrace):
    """``(program/op, start, end)`` of each operation, named by the program
    run that holds its start."""
    mods = sorted((m.start_ns, m.end_ns, program_name(m.name))
                  for m in chip.modules)
    starts = [m[0] for m in mods]
    for ev in chip.ops:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        prog = mods[i][2] + "/" if i >= 0 and ev.start_ns < mods[i][1] else ""
        yield prog + op_name(ev.name), ev.start_ns, ev.end_ns


def host_span_at(host, t_ns: int) -> str:
    """The innermost harness span (``call``, ``fetch``) that holds
    ``t_ns``, or ``"between"``."""
    best = None
    for ev in host:
        if ev.name in HOST_SPANS and ev.start_ns <= t_ns < ev.end_ns:
            if best is None or ev.dur_ns < best.dur_ns:
                best = ev
    return best.name if best is not None else "between"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ``top`` operations (``program/op``) by device seconds summed over
    the chips, and the ``top`` longest idle gaps of any chip, each named by
    the host span that held its midpoint."""
    lo, hi = trace.window
    per_op = Counter()
    gaps = []
    for chip in trace.chips:
        for name, start, end in _labelled_ops(chip):
            per_op[name] += max(0, min(end, hi) - max(start, lo))
        gaps += idle_gaps([(ev.start_ns, ev.end_ns) for ev in chip.ops],
                          lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in per_op.most_common(top)
                       if ns > 0],
        "idle_gaps": [[host_span_at(trace.host, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def roofline_share(bytes_moved: float, seconds: float,
                   peak_bytes_per_s: float):
    """Share, in percent, of the least time the bytes take at the peak
    bandwidth in the time measured; ``None`` where nothing was measured."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * (bytes_moved / peak_bytes_per_s) / seconds


def _events(line):
    return [Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for ev in line.events]


def from_profile_data(data) -> Trace:
    """The :class:`Trace` of a ``jax.profiler.ProfileData``: the TPU device
    planes in device order, and the host's annotation events. The window is
    the harness's ``window`` span, or the device events' extent where the
    trace has none."""
    chips, host = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = ChipTrace()
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.ops = _events(line)
                elif line.name == MODULES_LINE:
                    chip.modules = _events(line)
            chips[int(m.group(1))] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev.name in HOST_SPANS + (WINDOW,)]
    chips = [chips[i] for i in sorted(chips)]
    windows = [ev for ev in host if ev.name == WINDOW]
    if windows:
        window = (windows[0].start_ns, windows[0].end_ns)
    else:
        evs = [ev for c in chips for ev in c.ops]
        window = (min((e.start_ns for e in evs), default=0),
                  max((e.end_ns for e in evs), default=0))
    return Trace(chips=chips, host=host, window=window)


def load(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return from_profile_data(ProfileData.from_file(paths[0]))
