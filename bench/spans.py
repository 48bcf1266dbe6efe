"""The program's own spans and scopes in a profiler trace, for the metric
readers that read them.

The program (``src/repro/runtime/trace.py``) writes ``sort.*`` host spans
into the profiler's trace, on the device planes' clock, and names the
stages of its two device programs with ``jax.named_scope``: ``distribute``,
``bucket_scatter``, ``bucket_sort``, ``compact`` and ``rank_keys`` in
``_fused_sort_packed``; ``kway_ranks``, ``kway_starts``, ``kway_pad`` and
``kway_kernel`` in ``_kway_merge_jit``. ``tracereduce`` keeps neither.

Importing this module extends ``tracereduce.load``, the harness's one
reader of the trace file, so that the :class:`tracereduce.Trace` it returns
also carries

- ``spans``: the :class:`Span` of every ``sort.*`` event of every host
  thread, with its attributes;
- ``scoped_ops``: per chip, the :class:`ScopedOp` of each device operation
  that a program run holds, with the operation's scope path. The trace's
  events carry no scope; the ``/host:metadata`` plane holds the
  ``HloProto`` of every program that ran, whose instructions carry their
  ``op_name`` metadata, the scope path. An operation is matched by its
  program run and its instruction name.

The fields, readers, ``breakdown`` and printed lines of the trace stay as
they were. The readers below turn these into per-job numbers: device time
under a scope, chip idle time under the innermost span of the job's thread,
and the count of a span. Each is ``None`` where the trace has no device, or
lacks the spans or scopes it reads (a program from before they existed).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import tracereduce

SPAN_PREFIX = "sort."
# a span's name; on a CPU trace the XLA operations share the host plane,
# and a ``sort.21`` there is an HLO instruction
_SPAN_NAME = re.compile(r"^sort\.[a-z_]+$")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
INGEST = ("jit__fused_sort_packed",)
COMBINE = ("jit__kway_merge_jit", "jit__kway_take_jit")


@dataclass(frozen=True)
class Span:
    name: str          # with the prefix: "sort.dispatch"
    thread: str        # "<plane>#<line index>": one host thread
    start_ns: int
    dur_ns: int
    attrs: dict = field(default_factory=dict, compare=False)

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class ScopedOp:
    """One device operation, with the program that ran it and the
    ``op_name`` path of its instruction (``""`` where there is none)."""
    program: str
    scope: str
    start_ns: int
    end_ns: int


# -- reading ----------------------------------------------------------------

def host_spans(data) -> list:
    """The ``sort.*`` events of every host thread of a
    ``jax.profiler.ProfileData``."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [Span(ev.name, f"{plane.name}#{i}", int(ev.start_ns),
                         int(ev.duration_ns), dict(ev.stats))
                    for ev in line.events if _SPAN_NAME.match(ev.name)]
    return out


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a serialized protobuf
    message: an int for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for k, v in _fields(buf) if k == number), default)


def _text(buf, number: int) -> str:
    return bytes(_field(buf, number, b"")).decode()


def hlo_protos(xspace) -> dict:
    """Program run name (``jit_f(123)``, as the ``XLA Modules`` line names
    it) -> serialized ``HloProto``, from the ``/host:metadata`` plane of a
    serialized ``XSpace`` (field numbers of ``tsl/profiler/protobuf/
    xplane.proto``: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5; map entries key 1, value 2; XEventMetadata.name 2,
    .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1,
    .bytes_value 6)."""
    out = {}
    for k, plane in _fields(xspace):
        if k != 1 or _text(plane, 2) != METADATA_PLANE:
            continue
        stat_ids = [_field(_field(v, 2), 1, 0) for k2, v in _fields(plane)
                    if k2 == 5 and _text(_field(v, 2), 2) == HLO_PROTO_STAT]
        for k2, entry in _fields(plane):
            if k2 != 4:
                continue
            meta = _field(entry, 2)
            for k3, stat in _fields(meta):
                if k3 == 5 and _field(stat, 1, 0) in stat_ids:
                    out[_text(meta, 2)] = _field(stat, 6)
    return out


def op_scopes(hlo_proto) -> dict:
    """Instruction name -> its ``op_name`` metadata, over every computation
    of a serialized ``HloProto`` (``xla/service/hlo.proto``: HloProto
    .hlo_module 1; HloModuleProto.computations 3; HloComputationProto
    .instructions 2; HloInstructionProto.name 1, .metadata 7; OpMetadata
    .op_name 2)."""
    out = {}
    for k, comp in _fields(_field(hlo_proto, 1, b"")):
        if k != 3:
            continue
        for k2, ins in _fields(comp):
            if k2 == 2:
                meta = _field(ins, 7)
                out[_text(ins, 1)] = _text(meta, 2) if meta is not None \
                    else ""
    return out


def scoped_ops(trace, protos: dict) -> list:
    """Per chip, the :class:`ScopedOp` of each operation that a program
    run holds; an operation of a program with no ``HloProto`` in the trace
    gets the scope ``""``. The runs are labelled as ``tracereduce`` labels
    them, with each run's id kept in its name (``jit_f#123``): an
    instruction's name is unique only within one compiled program."""
    scopes = {run: op_scopes(p) for run, p in protos.items()}
    out = []
    for chip in trace.chips:
        keyed = tracereduce.ChipTrace(ops=chip.ops, modules=[
            tracereduce.Event(m.name.replace("(", "#", 1), m.start_ns,
                              m.dur_ns) for m in chip.modules])
        ops = []
        for label, start, end in tracereduce._labelled_ops(keyed):
            run, held, op = label.partition("/")
            if held:
                run = run.replace("#", "(", 1)
                ops.append(ScopedOp(tracereduce.program_name(run),
                                    scopes.get(run, {}).get(op, ""),
                                    start, end))
        out.append(ops)
    return out


def _keep_spans(from_profile_data):
    def with_spans(data):
        trace = from_profile_data(data)
        trace.spans = host_spans(data)
        return trace
    return with_spans


def _keep_scopes(load):
    def with_scopes(trace_dir):
        trace = load(trace_dir)
        # the one file that ``load`` read, for its ``/host:metadata`` plane
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as f:
            protos = hlo_protos(memoryview(f.read()))
        trace.scoped_ops = scoped_ops(trace, protos)
        return trace
    with_scopes.keeps_scopes = True
    return with_scopes


if not getattr(tracereduce.load, "keeps_scopes", False):
    tracereduce.from_profile_data = _keep_spans(tracereduce.from_profile_data)
    tracereduce.load = _keep_scopes(tracereduce.load)


# -- reduction --------------------------------------------------------------

def _per_job_ms(run, seconds):
    return seconds / len(run.latencies_s) * 1e3


def _job_spans(run):
    """The spans of the host threads that hold a ``sort.job``, or ``None``
    where the run has no job, its trace no device or no such thread."""
    trace = run.trace
    spans = getattr(trace, "spans", None)
    if not run.latencies_s or not spans or not trace.chips:
        return None
    threads = {s.thread for s in spans if s.name == SPAN_PREFIX + "job"}
    return [s for s in spans if s.thread in threads] or None


def scope_ms(run, programs, scope: str):
    """Device milliseconds per job, inside the window and summed over the
    chips, of the operations of the named programs under ``scope``; nested
    operations (a loop and its body) count once. ``None`` where no
    operation of those programs carries the scope."""
    trace = run.trace
    ops_per_chip = getattr(trace, "scoped_ops", None)
    if not run.latencies_s or not ops_per_chip:
        return None
    lo, hi = trace.window
    total, seen = 0, False
    for ops in ops_per_chip:
        iv = [(op.start_ns, op.end_ns) for op in ops
              if op.program in programs and scope in op.scope.split("/")]
        seen = seen or bool(iv)
        total += tracereduce.union_ns(iv, lo, hi)
    return _per_job_ms(run, total / 1e9) if seen else None


def innermost(spans) -> list:
    """``(start, end, span)`` pieces of time in which ``span`` is the
    innermost open span, for spans of one thread (which nest)."""
    pieces, stack, at = [], [], None

    def emit(end):
        if stack and end > at:
            pieces.append((at, end, stack[-1]))

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            at = stack.pop().end_ns
        emit(s.start_ns)
        stack.append(s)
        at = s.start_ns
    while stack:
        emit(stack[-1].end_ns)
        at = stack.pop().end_ns
    return pieces


def idle_ms(run, name: str):
    """Chip idle milliseconds per job inside the window, averaged over the
    chips, while a thread that holds ``sort.job`` had ``sort.<name>`` as
    its innermost span; spans of other threads (the staging worker) do not
    count. ``None`` where the job's thread has no such span."""
    spans = _job_spans(run)
    if spans is None:
        return None
    pieces = [(s, e) for t in {x.thread for x in spans}
              for s, e, span in innermost([x for x in spans if x.thread == t])
              if span.name == SPAN_PREFIX + name]
    if not pieces:
        return None
    trace = run.trace
    lo, hi = trace.window
    idle = 0
    for chip in trace.chips:
        gaps = tracereduce.idle_gaps(
            [(ev.start_ns, ev.end_ns) for ev in chip.ops], lo, hi)
        # the gaps' time inside the pieces: |gaps| + |pieces| - |both|
        idle += (tracereduce.union_ns(gaps, lo, hi)
                 + tracereduce.union_ns(pieces, lo, hi)
                 - tracereduce.union_ns(gaps + pieces, lo, hi))
    return _per_job_ms(run, idle / len(trace.chips) / 1e9)


def per_job(run, name: str):
    """How many ``sort.<name>`` spans of the job's thread start inside the
    window, per job."""
    spans = _job_spans(run)
    if spans is None:
        return None
    lo, hi = run.trace.window
    return sum(1 for s in spans if s.name == SPAN_PREFIX + name
               and lo <= s.start_ns < hi) / len(run.latencies_s)
