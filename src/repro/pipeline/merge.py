"""Run combiner: k-way merge of sorted lex-tuple runs on device.

A *run* here is a tuple of parallel 1-D arrays already sorted by the
lane-by-lane lexicographic order (``kernels/lex.py`` conventions — for the
word pipeline the tuple is ``(length, key_lane_0, ..., key_lane_L-1)``, i.e.
shortlex). The default combine is the ONE-launch streaming k-way merge
(``kernels.ops.merge_runs_lex`` over ``kernels/kway_kernel.py``): global
merge-path ranks split the output into blocks once, and the data streams
through a single pass — one scatter per lane off-TPU, or the
double-buffered Pallas streaming kernel on TPU.

The pre-PR-9 tournament tree (``engine='tournament'``: ceil(log2 k) rounds
of pairwise ``merge_sorted_lex``) is kept as the fallback and as the
differential oracle the tests hold the streaming path against — every round
is a full pass over all the data, which is exactly the log2(k)x HBM-traffic
multiple the streaming merge removes.

Both paths work in the *extended* representation: each run's packed compare
lanes (1-2 uint32 rank keys + keypack's minimal tie-break suffix) ride
alongside the data lanes, so ranking never re-packs. ``cmp_runs`` lets the
chunked ingest hand over rank keys the fused bucketize program already
computed.
"""

from __future__ import annotations

from ..kernels.keypack import packed_cmp_lanes
from ..kernels.ops import merge_runs_lex, merge_sorted_lex
from ..runtime.trace import span

__all__ = ["merge_two", "merge_runs"]

_ENGINES = ("auto", "kway", "kway_kernel", "tournament")


def merge_two(a_lanes, b_lanes, engine: str = "auto", max_values=None):
    """Merge two sorted lex-tuple runs (tuples of parallel 1-D arrays, may
    differ in length) into one sorted run. Thin alias of
    ``kernels.ops.merge_sorted_lex``, which validates arity and
    short-circuits empty runs without device work."""
    return merge_sorted_lex(tuple(a_lanes), tuple(b_lanes), engine=engine,
                            max_values=max_values)


def merge_runs(runs, engine: str = "auto", max_values=None, cmp_runs=None,
               manifests=None, supervisor=None,
               interpret: bool | None = None,
               block_size: int | None = None, counts=None):
    """k-way merge of sorted runs into one. ``runs``: list of sorted
    lex-tuple runs of equal arity; an empty list returns ``()`` and a single
    run is returned as-is — both without touching the device.

    ``engine`` picks the combine strategy:

    - ``'kway'`` (and ``'auto'``, which always resolves to it): ONE call
      into ``ops.merge_runs_lex`` — a single streaming pass for any k,
      executed through the supervisor stage ``'streaming_combine'``.
    - ``'kway_kernel'``: same, but forces the Pallas streaming kernel tier
      even where ``choose_kway_engine`` would pick the jnp scatter (the
      conformance matrix uses this to run the kernel under the interpreter).
    - ``'tournament'``: the legacy pairwise tree, ceil(log2 k) rounds each
      through supervisor stage ``'merge_round'`` — the fallback and the
      differential oracle; outputs are bit-identical across engines.

    ``cmp_runs``: optional parallel list of pre-packed compare-lane lists
    (e.g. ``SortedRun.cmp_lanes()`` — rank keys the fused per-chunk program
    already emitted); ``None`` packs them here via
    ``keypack.packed_cmp_lanes`` with ``max_values``. ``manifests``:
    optional parallel list of ``RunManifest``-likes; each run's element
    count is reconciled against its manifest *before* any device work, so a
    truncated/stale run (e.g. loaded from a resume store) fails loudly
    instead of merging short. ``supervisor``: optional
    ``runtime.SortSupervisor`` — combine stages are pure functions of their
    input runs, so a failed stage simply re-executes. ``interpret`` /
    ``block_size`` forward to the kernel tiers (``None`` = auto).

    ``counts``: runs of a fixed shape (the mesh exchange's slots) hold
    ``counts[r]`` real rows and then padding; the merged run then holds
    ``sum(counts)`` real rows first and padding after them, so a new input
    of the same shapes compiles nothing. The k-way engines only."""
    if engine not in _ENGINES:
        raise ValueError(f"unknown merge_runs engine {engine!r}")
    if counts is not None and engine == "tournament":
        raise ValueError("counts need a k-way engine, not 'tournament'")
    runs = [tuple(r) for r in runs]
    if manifests is not None:
        from .validate import ValidationError
        if len(manifests) != len(runs):
            raise ValueError("manifests must parallel runs")
        for r, m in zip(runs, manifests):
            if r and int(r[0].shape[0]) != m.count:
                raise ValidationError(
                    f"run {m.chunk_id}: {int(r[0].shape[0])} element(s) "
                    f"but manifest records {m.count} — refusing to merge")
    if not runs:
        return ()
    if len(runs) == 1:
        return runs[0]
    arity = len(runs[0])
    if any(len(r) != arity for r in runs):
        raise ValueError("runs must have the same lane arity")
    if cmp_runs is None:
        cmp_runs = [packed_cmp_lanes(list(r), max_values) for r in runs]
    ext = [tuple(c) + r for c, r in zip(cmp_runs, runs)]
    n_cmp = len(ext[0]) - arity
    rows = (int(sum(counts)) if counts is not None
            else sum(int(r[0].shape[0]) for r in runs))

    if engine != "tournament":
        ops_engine = "kernel" if engine == "kway_kernel" else "auto"

        def combine(ext_rs):
            return merge_runs_lex(ext_rs, engine=ops_engine, n_cmp=n_cmp,
                                  block_size=block_size,
                                  interpret=interpret, counts=counts)

        with span("streaming_combine", runs=len(ext), rows=rows):
            if supervisor is None:
                merged = combine(ext)
            else:
                merged = supervisor.run_stage("streaming_combine", combine,
                                              ext)
        return tuple(merged[n_cmp:])

    def one_round(ext_rs):
        with span("merge_round", runs=len(ext_rs), rows=rows):
            nxt = [merge_sorted_lex(ext_rs[i], ext_rs[i + 1], n_cmp=n_cmp,
                                    interpret=interpret)
                   for i in range(0, len(ext_rs) - 1, 2)]
        if len(ext_rs) % 2:
            nxt.append(ext_rs[-1])
        return nxt

    while len(ext) > 1:
        if supervisor is None:
            ext = one_round(ext)
        else:
            ext = supervisor.run_stage("merge_round", one_round, ext)
    return ext[0][n_cmp:]
