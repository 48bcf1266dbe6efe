"""The program's spans and scopes in the trace (``bench/spans.py``) and the
six readers built on them, on hand-built traces whose idle gaps, spans and
scoped operations are known, on a trace recorded on the CPU, and through
the harness's own traced run; and the harness's reduction, readers and
breakdown unchanged by a trace that carries the program's spans."""

import glob
import types

import jax
import jax.numpy as jnp
import pytest

import harness
import spans as sp
import tracereduce as tr
from test_perfbench_harness import checkout  # noqa: F401 (a fixture)
from test_perfbench_trace import _run, _trace

E = tr.Event
JOB, WORKER = "/host:CPU#1", "/host:CPU#0"
READERS = ("combine_ranks_ms", "combine_kernel_ms", "ingest_compact_ms",
           "host_syncs_per_job", "idle_dispatch_ms", "idle_sync_ms")
HARNESS_READERS = ("device_idle_share", "ingest_ms", "ingest_roofline",
                   "combine_ms", "combine_roofline")


def _read(name, run):
    return harness.load_module("metrics", name).read(run)


def _span(name, start, end, thread=JOB, **attrs):
    return sp.Span("sort." + name, thread, start, end - start, attrs)


def _spans():
    # the job thread: a chunk whose launch and count read nest in it, then
    # a dispatch outside any chunk; the staging worker spans everything
    return [_span("job", 0, 100, rows=9, chunks=1),
            _span("ingest_chunk", 10, 40, chunk=0),
            _span("dispatch", 10, 15, program="_fused_sort_packed"),
            _span("sync", 15, 30, what="max_count"),
            _span("dispatch", 50, 60, program="run_lanes"),
            _span("stage", 0, 100, thread=WORKER, chunk=0)]


def _scoped():
    k = "jit(_kway_merge_jit)/"
    return [[sp.ScopedOp("jit__kway_merge_jit", k + "kway_ranks/while", 0,
                         50),
             sp.ScopedOp("jit__kway_merge_jit",
                         k + "kway_ranks/while/body/gather", 10, 20),
             sp.ScopedOp("jit__kway_merge_jit", k + "kway_kernel/pallas", 50,
                         60),
             sp.ScopedOp("jit__kway_merge_jit", k + "kway_ranksx/add", 60,
                         62),
             sp.ScopedOp("jit_other", k + "kway_kernel/add", 62, 64),
             sp.ScopedOp("jit__fused_sort_packed",
                         "jit(_fused_sort_packed)/compact/scatter", 64, 90)],
            []]


def _traced(spans=None, scoped=None, window=(0, 100)):
    """Two jobs on two chips: chip 0 idles on [5, 20), [25, 40) and
    [45, 70); chip 1 never. The trace carries what ``tracereduce.load``
    adds once this module is imported."""
    busy = tr.ChipTrace(ops=[E("a", 0, 5), E("b", 20, 5), E("c", 40, 5),
                             E("d", 70, 30)])
    full = tr.ChipTrace(ops=[E("e", 0, 100)])
    trace = tr.Trace(chips=[busy, full], host=[], window=window)
    trace.spans = _spans() if spans is None else spans
    trace.scoped_ops = _scoped() if scoped is None else scoped
    return _run(trace, jobs=2)


def _per_job_ms(ns):
    return ns / 1e9 / 2 * 1e3


def test_innermost_pieces_of_nested_spans():
    pieces = [(s, e, x.name[5:]) for s, e, x in sp.innermost(
        [x for x in _spans() if x.thread == JOB])]
    assert pieces == [(0, 10, "job"), (10, 15, "dispatch"),
                      (15, 30, "sync"), (30, 40, "ingest_chunk"),
                      (40, 50, "job"), (50, 60, "dispatch"),
                      (60, 100, "job")]


def test_idle_goes_to_the_innermost_span_of_the_job_thread():
    # chip 0: [5, 20) is job 5, dispatch 5, sync 5; [25, 40) is sync 5,
    # ingest_chunk 10; [45, 70) is job 5, dispatch 10, job 10. Chip 1
    # never idles, so each halves; the worker's stage gets nothing.
    run = _traced()
    for name, ns in (("job", 10), ("dispatch", 7.5), ("sync", 5),
                     ("ingest_chunk", 5)):
        assert sp.idle_ms(run, name) == pytest.approx(_per_job_ms(ns)), name
    assert sp.idle_ms(run, "stage") is None
    assert _read("idle_dispatch_ms", run) == pytest.approx(_per_job_ms(7.5))
    assert _read("idle_sync_ms", run) == pytest.approx(_per_job_ms(5))
    # the staging worker alone holds no job
    assert sp.idle_ms(_traced(spans=[_spans()[-1]]), "stage") is None


def test_scope_ms_counts_nested_ops_once_and_clips_to_the_window():
    run = _traced(window=(0, 75))
    assert sp.scope_ms(run, sp.COMBINE, "kway_ranks") == \
        pytest.approx(_per_job_ms(50))
    assert sp.scope_ms(run, sp.COMBINE, "kway_pad") is None
    assert _read("combine_ranks_ms", run) == pytest.approx(_per_job_ms(50))
    assert _read("combine_kernel_ms", run) == pytest.approx(_per_job_ms(10))
    assert _read("ingest_compact_ms", run) == pytest.approx(_per_job_ms(11))


def test_host_syncs_count_the_job_thread_in_the_window():
    assert _read("host_syncs_per_job", _traced()) == 0.5
    assert _read("host_syncs_per_job", _traced(window=(20, 100))) == 0
    worker_sync = _spans() + [_span("sync", 50, 55, thread=WORKER)]
    assert _read("host_syncs_per_job", _traced(spans=worker_sync)) == 0.5


@pytest.mark.parametrize("trace", ["parent", "no_device", "untraced"])
def test_every_reader_is_none_without_its_spans_or_scopes(trace):
    """A trace of a program that writes no span and names no scope (the
    parent's), a trace with no device plane (the CPU's) and no trace at
    all give ``None`` for each reader, never 0."""
    if trace == "parent":
        run = _traced(spans=[], scoped=[[sp.ScopedOp(
            "jit__kway_merge_jit", "jit(_kway_merge_jit)/while", 0, 50)],
            []])
    elif trace == "no_device":
        run = _traced()
        run.trace.chips, run.trace.scoped_ops = [], []
    else:
        run = _run(None)
    for name in READERS:
        assert _read(name, run) is None, name


def _profile(with_spans):
    def ev(name, start, dur, **stats):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur, stats=stats.items())

    def line(name, evs):
        return types.SimpleNamespace(name=name, events=evs)

    program = [ev("sort.job", 0, 60, rows=9, chunks=1),
               ev("sort.sync", 20, 10, what="max_count")]
    host = [ev("window", 0, 65), ev("call", 0, 35), ev("fetch", 35, 30)]
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Ops", [ev("fusion.1", 0, 10), ev("sort.21", 5, 15),
                             ev("custom-call.3", 40, 10)]),
            line("XLA Modules", [ev("jit__fused_sort_packed(1)", 0, 25),
                                 ev("jit__kway_merge_jit(7)", 40, 10)])]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python", host + (program if with_spans else [])),
            line("tf_XLAPjRtCpuClient", [ev("sort.21", 5, 1)])]),
    ])


def test_the_harness_reduction_ignores_the_program_spans():
    """The harness's trace, breakdown and device readers read the same
    numbers whether or not the program wrote its spans, and the trace
    carries the spans besides."""
    with_spans = tr.from_profile_data(_profile(True))
    without = tr.from_profile_data(_profile(False))
    assert with_spans == without
    assert tr.breakdown(with_spans) == tr.breakdown(without)
    for name in HARNESS_READERS:
        assert _read(name, _run(with_spans)) == _read(name, _run(without))
        assert _read(name, _run(_trace())) is not None
    assert [(s.name, s.start_ns, s.attrs) for s in with_spans.spans] == [
        ("sort.job", 0, {"rows": 9, "chunks": 1}),
        ("sort.sync", 20, {"what": "max_count"})]
    assert without.spans == []


def test_scoped_ops_are_labelled_by_program_run():
    """Two compiled programs of one name may give one instruction name two
    scopes: an operation takes the scope of the run that holds it."""
    chip = tr.ChipTrace(
        ops=[E("%fusion.1 = u32[8] fusion(...)", 0, 10), E("fusion.1", 20, 5),
             E("fusion.1", 40, 5)],
        modules=[E("jit__fused_sort_packed(11)", 0, 15),
                 E("jit__fused_sort_packed(22)", 18, 10)])
    trace = tr.Trace(chips=[chip], host=[], window=(0, 50))
    protos = {"jit__fused_sort_packed(11)": {"fusion.1": "a/compact/x"},
              "jit__fused_sort_packed(22)": {"fusion.1": "a/bucket_sort/y"}}
    real = sp.op_scopes
    sp.op_scopes = lambda scopes: scopes
    try:
        (ops,) = sp.scoped_ops(trace, protos)
    finally:
        sp.op_scopes = real
    assert ops == [
        sp.ScopedOp("jit__fused_sort_packed", "a/compact/x", 0, 10),
        sp.ScopedOp("jit__fused_sort_packed", "a/bucket_sort/y", 20, 25)]


def test_scopes_come_from_a_recorded_trace(tmp_path):
    """The ``/host:metadata`` plane of a CPU trace holds each program's
    HLO, whose instructions carry their scope paths; ``tracereduce.load``
    reads the spans and scoped operations of the same file."""
    @jax.jit
    def f(x):
        with jax.named_scope("alpha"):
            y = jnp.sort(x) * 2
        with jax.named_scope("beta"):
            return jnp.cumsum(y)

    x = jnp.arange(64.0)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        protos = sp.hlo_protos(memoryview(fh.read()))
    (run,) = [name for name in protos if name.startswith("jit_f(")]
    paths = set(sp.op_scopes(protos[run]).values())
    assert any(p.startswith("jit(f)/alpha/") for p in paths)
    assert any(p.startswith("jit(f)/beta/") for p in paths)
    trace = tr.load(str(tmp_path))
    assert trace.spans == [] and trace.scoped_ops == [] and trace.chips == []


def test_a_traced_cell_carries_the_spans_to_its_readers(
        checkout, capsys, monkeypatch):  # noqa: F811
    """The harness's traced run hands its readers a trace with the spans:
    on the CPU no device, so no reader reads a number, but the job thread
    holds one host sync per chunk (600 words in chunks of 256)."""
    runs, load = [], harness.load_module

    def load_recording(kind, name):
        module = load(kind, name)
        if name == "host_syncs_per_job":
            read = module.read
            module.read = lambda run: runs.append(run) or read(run)
        return module

    monkeypatch.setattr(harness, "load_module", load_recording)
    assert harness.main(["--workload", "tiny-kway", "--seed", "13",
                         "--seconds", "0.2", "--trace", "1"]) == 0
    assert '"metrics": {}' in capsys.readouterr().out.strip().splitlines()[-1]
    (run,) = runs
    assert run.trace.chips == [] and sp.per_job(run, "sync") is None
    run.trace.chips = [tr.ChipTrace()]
    assert sp.per_job(run, "sync") == 3
    assert sp.per_job(run, "ingest_chunk") == 3
