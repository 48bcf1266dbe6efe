#!/usr/bin/env python3
"""Smoke run of the word-sort pipeline on TPU, end to end through its entry
points, at a size its users would call real.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh paths, on four chips

One chip: 2^24 seeded words (lowercase ASCII, lengths 1-15, duplicates
allowed), packed to the 4-lane uint32 layout of ``core/packing.py``, go
through ``pipeline.chunked_sort_packed`` with its defaults (the fused
distribute -> segmented sort -> shortlex compaction program per chunk, then
the k-way combine), and the result must equal a NumPy shortlex lexsort
element for element.

Four chips: the same input through ``distributed_chunked_sort_lex`` (one
chunk per chip, run exchange, per-destination combine, sharded spill) and
through ``distributed_sort_lex(engine='sample')`` on a 4-device mesh, each
compared with the same reference.

The script refuses to run anywhere but on a TPU with natively compiled
Pallas kernels: it exits non-zero, before any phase and without a result
line, when JAX finds no TPU. Every phase's failure exits non-zero. The last
line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

MAX_WORD_LEN = 15        # the paper's max_word_len (configs/paper_sort.py)
LANES = 4                # uint32 lanes of a packed 15-byte word
WORDS = 1 << 24
CHUNK = 1 << 20          # the largest chunk that compiles: 16 runs


def make_words(n: int, seed: int):
    """``n`` random lowercase words of 1..15 letters, packed big-endian into
    (n, 4) uint32 lanes without a per-word host loop. Returns
    ``(keys, lengths)``; duplicates occur (26 one-letter words exist)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, MAX_WORD_LEN + 1, n, dtype=np.int32)
    chars = rng.integers(ord("a"), ord("z") + 1, (n, 4 * LANES),
                         dtype=np.uint8)
    chars[np.arange(4 * LANES)[None, :] >= lengths[:, None]] = 0
    b = chars.reshape(n, LANES, 4).astype(np.uint32)
    keys = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    return keys, lengths


def reference_order(keys, lengths):
    """Shortlex order of the packed words: length first, then the key lanes
    (np.lexsort takes its primary key last)."""
    return np.lexsort(tuple(keys[:, l] for l in reversed(range(LANES)))
                      + (lengths,))


def check_result(out_lengths, out_keys, keys, lengths, order, what):
    """Raise unless the sorted output equals the reference element for
    element (equal shortlex tuples are equal words, so any correct sort
    matches the lexsort order exactly)."""
    out_lengths = np.asarray(out_lengths)
    out_keys = np.asarray(out_keys)
    if out_keys.shape != keys.shape:
        raise AssertionError(f"{what}: shape {out_keys.shape} != {keys.shape}")
    bad = np.flatnonzero((out_lengths != lengths[order])
                         | np.any(out_keys != keys[order], axis=1))
    if bad.size:
        i = int(bad[0])
        raise AssertionError(
            f"{what}: {bad.size} row(s) differ from the NumPy reference, "
            f"first at {i}: got {out_lengths[i]} {out_keys[i].tolist()}, "
            f"want {lengths[order][i]} {keys[order][i].tolist()}")


def say(msg):
    """One line of progress on standard output, flushed at once."""
    print(msg, flush=True)


# JAX's duration event for one backend compile (a persistent-cache hit
# included: the event spans the cache lookup), and its count of those hits
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """JAX's compile events while the context is open: seconds by program
    name, and hits in the persistent compilation cache."""

    def __init__(self):
        self.seconds = collections.Counter()
        self.hits = 0

    def _on_duration(self, event, duration, fun_name="", **_):
        if event == COMPILE_EVENT:
            self.seconds[fun_name] += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def total(self):
        return sum(self.seconds.values())

    def __str__(self):
        names = ", ".join(f"{name} {sec:.3f} s"
                          for name, sec in self.seconds.most_common())
        return (f"{self.total():.3f} s [{names or 'nothing compiled'}], "
                f"{self.hits} persistent-cache hit(s)")


def timed(fn, *args, **kw):
    """``fn(*args)``, its wall seconds up to ``block_until_ready`` (of a
    SortedRun's lanes, which are not a pytree of their own), and the
    :class:`CompileLog` of that call."""
    import jax
    with CompileLog() as compiles:
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out.lanes() if hasattr(out, "lanes") else out)
        wall = time.perf_counter() - t0
    return out, wall, compiles


def run_chunked(keys, chunk, log=say):
    """The one-chip main path: the per-chunk program alone on the first
    chunk (cold) and the second (warm), then ``chunked_sort_packed`` once
    over everything. The per-chunk program is the one
    ``chunked_sort_packed`` runs for every full chunk, with ``chunk`` slots
    per bucket, so inside it only the combine (and a short tail chunk's
    program) compiles. The combine's run time is derived: end to end less
    compiles and ``n_chunks`` warm chunk runs. Returns the
    :class:`SortedRun`."""
    from repro.pipeline import chunked_sort_packed, sorted_run

    n_chunks = -(-keys.shape[0] // chunk)
    (_, cold, cold_comp), (_, warm, warm_comp) = [
        timed(sorted_run, keys[s:s + chunk], capacity=chunk)
        for s in (0, chunk)]
    warm_run = warm - warm_comp.total()
    log(f"phase chunk_sort: {n_chunks} chunk(s) of {chunk}, {chunk} slots "
        f"per bucket; first chunk run {cold - cold_comp.total():.3f} s, "
        f"compile {cold_comp}; second chunk run {warm_run:.3f} s, compile "
        f"{warm_comp}")
    merged, wall, comp = timed(chunked_sort_packed, keys, chunk_size=chunk)
    log(f"phase chunked_sort_packed: end to end {wall:.3f} s, compile "
        f"{comp}")
    log(f"phase combine: {n_chunks}-way; run ~"
        f"{wall - comp.total() - warm_run * n_chunks:.3f} s (derived: end "
        f"to end less compile and {n_chunks} x the second chunk's run)")
    return merged


def count_kernels(chunk_keys, chunk):
    """``tpu_custom_call`` ops in the compiled per-chunk program's HLO: the
    Pallas kernels inside it."""
    from repro.core.bucketing import _fused_sort_packed
    compiled = _fused_sort_packed.lower(
        chunk_keys, capacity=chunk, algorithm="pallas").compile()
    return compiled.as_text().count("tpu_custom_call")


def run_mesh(keys, lengths, order, devices, log=say):
    """The four-chip paths, each run once and checked against the
    reference: the out-of-core chunk-per-device sort with a sharded spill,
    and the sample sort on a mesh."""
    import jax.numpy as jnp
    from repro.core.distributed import (distributed_chunked_sort_lex,
                                        distributed_sort_lex)
    from repro.parallel.compat import AxisType, mesh_from_devices
    from repro.pipeline import ShardStore

    def chunked():
        with tempfile.TemporaryDirectory() as tmp:
            out = distributed_chunked_sort_lex(
                keys, devices=devices, shard_store=ShardStore(tmp),
                validate="cheap")
            shards = [out.load_shard(i) for i in range(len(out.manifests))]
            return (np.concatenate([np.asarray(s.lengths) for s in shards]),
                    np.concatenate([np.asarray(s.keys) for s in shards]))

    mesh = mesh_from_devices(np.asarray(devices), ("data",),
                             axis_types=(AxisType.Auto,))
    lanes = [jnp.asarray(lengths)] + [jnp.asarray(keys[:, l])
                                      for l in range(LANES)]

    def sample():
        return distributed_sort_lex(lanes, mesh, axis="data", engine="sample")

    for name, fn in (("distributed_chunked_sort_lex", chunked),
                     ("distributed_sort_lex(sample)", sample)):
        out, wall, comp = timed(fn)
        where = ("" if isinstance(out[0], np.ndarray) else
                 f"; output on {sorted(out[0].devices(), key=str)}")
        log(f"phase {name}: {len(devices)} devices; run "
            f"{wall - comp.total():.3f} s, compile {comp}{where}")
        if isinstance(out[0], np.ndarray):
            out_lengths, out_keys = out
        else:
            out_lengths = out[0]
            out_keys = np.stack([np.asarray(o) for o in out[1:]], axis=1)
        check_result(out_lengths, out_keys, keys, lengths, order, name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro.kernels import ops
    from repro.launch.cache import use_compile_cache

    cache = use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or ops.pallas_lowering() != "compiled":
        print(f"chip_smoke: needs a TPU with compiled Pallas kernels; JAX "
              f"found {devices[0].platform} (pallas "
              f"{ops.pallas_lowering()})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    logging.basicConfig(stream=sys.stdout, level=logging.WARNING,
                        format="%(name)s %(levelname)s %(message)s")
    logging.getLogger("repro.core").setLevel(logging.INFO)

    say(f"devices: {jax.devices()}")
    say(f"provenance: {ops.execution_provenance()}")
    say(f"compile cache: {cache}")
    with CompileLog() as compiles:
        t0 = time.perf_counter()
        keys, lengths = make_words(WORDS, args.seed)
        say(f"input: {WORDS} words, {keys.nbytes + lengths.nbytes} bytes "
            f"of keys and lengths, seed {args.seed}; generated in "
            f"{time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        order = reference_order(keys, lengths)
        say(f"reference: numpy lexsort {time.perf_counter() - t0:.3f} s")

        if args.chips == 1:
            say(f"chunk size C: {CHUNK}")
            merged = run_chunked(keys, CHUNK)
            check_result(merged.lengths, merged.keys, keys, lengths, order,
                         "chunked_sort_packed")
            say("check: chunked_sort_packed matches the reference")
            t0 = time.perf_counter()
            n_kernels = count_kernels(jax.device_put(keys[:CHUNK]), CHUNK)
            say(f"per-chunk program: {n_kernels} tpu_custom_call op(s) in "
                f"its compiled HLO ({time.perf_counter() - t0:.3f} s to "
                f"fetch)")
            if n_kernels == 0:
                raise AssertionError("no Pallas kernel in the per-chunk "
                                     "program")
        else:
            run_mesh(keys, lengths, order, devices)
            say("check: both mesh paths match the reference")
    say(f"compile, whole run: {compiles}")

    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
