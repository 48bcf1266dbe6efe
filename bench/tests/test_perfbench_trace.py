"""The reduction from trace to metrics, on hand-built event lists whose
union, gaps and program totals are known."""

import types

import pytest

import tracereduce as tr
from harness import Cell, Run

E = tr.Event


def _chip():
    # ops: [0,10) and [5,20) overlap, [20,25) abuts, [40,50) after a gap,
    # [60,70) half outside a window of [0,65)
    ops = [E("fusion.1", 0, 10), E("sort.2", 5, 15), E("fusion.1", 20, 5),
           E("custom-call.3", 40, 10), E("fusion.1", 60, 10)]
    modules = [E("jit__fused_sort_packed(1)", 0, 25),
               E("jit__kway_merge_jit(7)", 40, 10),
               E("jit__fused_sort_packed(1)", 60, 10),
               E("jit_concatenate(2)", 26, 2)]
    return tr.ChipTrace(ops=ops, modules=modules)


def _trace():
    host = [E("window", 0, 65), E("call", 0, 35), E("fetch", 35, 30)]
    quiet = tr.ChipTrace(ops=[E("fusion.9", 0, 13)],
                         modules=[E("jit__fused_sort_packed(1)", 0, 13)])
    return tr.Trace(chips=[_chip(), quiet], host=host, window=(0, 65))


def test_union_counts_overlap_once_and_clips_to_the_window():
    iv = [(e.start_ns, e.end_ns) for e in _chip().ops]
    assert tr.union_ns(iv, 0, 65) == 25 + 10 + 5
    assert tr.union_ns(iv, 0, 100) == 25 + 10 + 10
    assert tr.union_ns([(0, 10), (2, 3), (9, 12)], 0, 100) == 12
    assert tr.union_ns([], 0, 10) == 0


def test_idle_gaps_cover_the_rest_of_the_window():
    iv = [(e.start_ns, e.end_ns) for e in _chip().ops]
    gaps = tr.idle_gaps(iv, 0, 65)
    assert gaps == [(25, 40), (50, 60)]
    assert tr.union_ns(iv, 0, 65) + sum(e - s for s, e in gaps) == 65
    assert tr.idle_gaps([], 3, 9) == [(3, 9)]


def test_program_totals_by_name_and_window():
    chip = _chip()
    assert tr.program_name("jit__kway_merge_jit(7)") == "jit__kway_merge_jit"
    assert tr.module_ns(chip, ["jit__fused_sort_packed"], 0, 65) == 25 + 5
    assert tr.module_ns(chip, ["jit__kway_merge_jit", "jit__kway_take_jit"],
                        0, 65) == 10
    assert tr.program_seconds(_trace(), ["jit__fused_sort_packed"]) == \
        pytest.approx((30 + 13) / 1e9)


def test_breakdown_ranks_ops_and_labels_gaps_by_host_span():
    b = tr.breakdown(_trace())
    # the fused program's fusion.1: 10 + 5 + the 5 of [60, 70) inside the
    # window; sort.2 starts inside its run too; fusion.9 on chip 1
    assert b["device_ops"][0] == ["jit__fused_sort_packed/fusion.1",
                                  pytest.approx(20 / 1e9)]
    assert [name for name, _ in b["device_ops"]] == [
        "jit__fused_sort_packed/fusion.1", "jit__fused_sort_packed/sort.2",
        "jit__fused_sort_packed/fusion.9", "jit__kway_merge_jit/custom-call.3"]
    # chip 1 is idle on [13, 65): mid 39 lies in fetch; chip 0's gaps
    # [25, 40) (mid 32, call) and [50, 60) (mid 55, fetch)
    assert b["idle_gaps"] == [["fetch", pytest.approx(52 / 1e9)],
                              ["call", pytest.approx(15 / 1e9)],
                              ["fetch", pytest.approx(10 / 1e9)]]
    assert tr.host_span_at([], 5) == "between"


def test_op_names_are_short_and_outside_a_program_unlabelled():
    assert tr.op_name("%fusion.3 = u32[8]{0} fusion(u32[8]{0} %a)") == \
        "fusion.3"
    chip = tr.ChipTrace(ops=[E("%copy.1 = u32[2] copy(%x)", 30, 1)],
                        modules=[E("jit_f(1)", 0, 10)])
    assert [n for n, _, _ in tr._labelled_ops(chip)] == ["copy.1"]


def test_roofline_share():
    # 819 bytes at 819 B/s take 1 s: in 4 s that is 25%
    assert tr.roofline_share(819, 4.0, 819.0) == pytest.approx(25.0)
    assert tr.roofline_share(819, 0.0, 819.0) is None


def _profile(planes):
    def line(name, evs):
        return types.SimpleNamespace(name=name, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p, lines=[line(n, e) for n, e in ls])
        for p, ls in planes])


def test_profile_planes_become_chips_in_device_order():
    data = _profile([
        ("/device:TPU:1", [("XLA Ops", [("b", 5, 5)]),
                           ("XLA Modules", [("jit_g(2)", 5, 5)])]),
        ("/device:TPU:0", [("XLA Ops", [("a", 0, 4)]), ("Steps", [])]),
        ("/device:TPU:0 SparseCore 0", [("XLA Ops", [("x", 0, 99)])]),
        ("/host:CPU", [("python", [("window", 0, 20), ("call", 1, 3),
                                   ("other", 0, 1)])]),
    ])
    trace = tr.from_profile_data(data)
    assert [c.ops[0].name for c in trace.chips] == ["a", "b"]
    assert trace.chips[1].modules[0].name == "jit_g(2)"
    assert trace.window == (0, 20)
    assert [e.name for e in trace.host] == ["window", "call"]


def test_profile_without_a_window_span_spans_the_device_events():
    data = _profile([("/device:TPU:0", [("XLA Ops", [("a", 3, 4),
                                                     ("b", 10, 2)])])])
    assert tr.from_profile_data(data).window == (3, 12)


def _run(trace, jobs=2):
    cell = Cell(name="c", spec={}, config={}, end_to_end=[], per_layer=[])
    return Run(cell=cell, peaks={"hbm_bytes_per_s": 36e6},
               words_per_job=1000, latencies_s=[0.5] * jobs, window_s=1.0,
               setup_s=7.5, trace=trace)


def test_device_readers():
    import harness
    read = {name: harness.load_module("metrics", name).read for name in (
        "device_idle_share", "ingest_ms", "ingest_roofline", "combine_ms",
        "combine_roofline", "words_per_s", "job_p90_ms", "setup_s")}
    run = _run(_trace())
    # chip 0 busy 40 of 65, chip 1 busy 13 of 65
    assert read["device_idle_share"](run) == pytest.approx(
        ((1 - 40 / 65) + (1 - 13 / 65)) / 2)
    # ingest: 43 ns over the chips, for 2 jobs
    assert read["ingest_ms"](run) == pytest.approx(43e-9 / 2 * 1e3)
    # 36 B x 1000 words at 36e6 B/s take 1 ms, in 21.5 ns per job
    assert read["ingest_roofline"](run) == pytest.approx(
        100 * 1e-3 / (43e-9 / 2))
    assert read["combine_ms"](run) == pytest.approx(10e-9 / 2 * 1e3)
    assert read["words_per_s"](run) == 2000
    assert read["job_p90_ms"](run) == pytest.approx(500)
    assert read["setup_s"](run) == 7.5
    # nothing traced, or the program absent: the metric is left out, not 0
    untraced = _run(None)
    for name in ("device_idle_share", "ingest_ms", "ingest_roofline",
                 "combine_ms", "combine_roofline"):
        assert read[name](untraced) is None
    no_combine = _run(tr.Trace(chips=[tr.ChipTrace()], host=[],
                               window=(0, 10)))
    assert read["combine_ms"](no_combine) is None
    assert read["combine_roofline"](no_combine) is None
