"""Out-of-core distributed chunked sort (``distributed_chunked_sort_lex``):
chunk-per-device ingest -> one exact-count run exchange -> one-launch
streaming k-way combine per destination. The mesh-scale cases ride the
8-fake-device subprocess pattern of ``test_distributed_sort.py`` /
``test_sortfault.py``; every output is held bit-identical to the
single-process pipeline and the NumPy shortlex oracle.

Sizes stay small (~500 words, per-device chunks of 64): every chunk
compiles an interpret-mode Pallas program on this CPU container.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import distributed_chunked_sort_lex
from repro.core.packing import pack_words, unpack_words
from repro.pipeline import chunked_sort_packed


def _run_multidev(script, timeout=600, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_COMMON = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import distributed_chunked_sort_lex
from repro.core.packing import pack_words, unpack_words

assert len(jax.devices()) == 8
rng = np.random.default_rng(0)
alpha = list("abcdefgh")
words = ["".join(rng.choice(alpha, l)) for l in rng.integers(0, 9, 509)]
keys = np.asarray(pack_words(words))

def assert_runs_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
    np.testing.assert_array_equal(np.asarray(a.lengths),
                                  np.asarray(b.lengths))
"""


def test_distributed_chunked_bit_identical_to_oracle():
    """509 words over 8 devices: every per-device chunk holds at most 64
    rows, so the input is larger than any single chunk capacity — and the
    global result must equal both the single-process chunked pipeline and
    the NumPy shortlex oracle bit-for-bit, with ``validate='full'`` green."""
    out = _run_multidev(_COMMON + """
from repro.pipeline import chunked_sort_packed

run = distributed_chunked_sort_lex(keys, validate="full")
assert int(run.keys.shape[0]) == 509
oracle = chunked_sort_packed(jnp.asarray(keys), chunk_size=64)
assert_runs_equal(run, oracle)
shortlex = sorted(words, key=lambda w: (len(w.encode()), w.encode()))
assert unpack_words(np.asarray(run.keys)) == shortlex
print("DIST_CHUNKED_OK")
""")
    assert "DIST_CHUNKED_OK" in out


def test_exchange_and_combine_failures_recover_bit_identical():
    """Injected ``StageFailure`` mid run-exchange and mid streaming-combine:
    both stages are pure functions of their input runs, so supervised retry
    must recover output bit-identical to the no-failure run."""
    out = _run_multidev(_COMMON + """
from repro.runtime import RetryPolicy, SortSupervisor, StageFailureInjector

oracle = distributed_chunked_sort_lex(keys)
inj = StageFailureInjector(fail_at={"run_exchange": {0},
                                    "streaming_combine": {0, 2}})
sup = SortSupervisor(policy=RetryPolicy(max_retries=3), injector=inj)
run = distributed_chunked_sort_lex(keys, supervisor=sup, validate="full")
assert_runs_equal(run, oracle)
assert ("run_exchange", 0, "transient") in inj.fired
assert ("streaming_combine", 0, "transient") in inj.fired
assert [e.action for e in sup.events] == ["retry"] * 3
print("FAULTS_OK")
""")
    assert "FAULTS_OK" in out


def test_overflow_policies_raise_retry_clip():
    """Destination-capacity overflow paths: 'raise' reports the required
    size, 'retry' doubles capacity (and sample density) until lossless even
    under unsplittable total skew, 'clip' keeps each destination's capacity
    smallest elements and stays sorted."""
    out = _run_multidev(_COMMON + """
from repro.runtime import CapacityOverflow

try:
    distributed_chunked_sort_lex(keys, capacity=30, on_overflow="raise")
    raise SystemExit("expected CapacityOverflow")
except CapacityOverflow as e:
    assert e.capacity == 30 and e.required > 30

# unsplittable skew: one word repeated — every splitter equal, one
# destination takes everything; retry must still terminate (capacity
# doubling is bounded by n) and come back lossless
dup = np.asarray(pack_words(["abc"] * 400))
oracle = distributed_chunked_sort_lex(dup)
run = distributed_chunked_sort_lex(dup, capacity=80, on_overflow="retry",
                                   validate="full")
assert_runs_equal(run, oracle)

clip = distributed_chunked_sort_lex(dup, capacity=30, on_overflow="clip",
                                    validate="cheap")
assert int(clip.keys.shape[0]) == 30
assert np.all(np.diff(np.asarray(clip.lengths)) >= 0)
print("OVERFLOW_OK")
""")
    assert "OVERFLOW_OK" in out


def test_store_resume_skips_completed_runs():
    """PR 6's manifests survive the distributed path: a job killed mid
    ingest resumes from its persisted per-device runs (only the missing
    chunks launch), and a fully-persisted store resumes with zero
    launches — output bit-identical throughout."""
    out = _run_multidev(_COMMON + """
import tempfile
from unittest import mock
import repro.pipeline.ingest as ingest_mod
from repro.pipeline import RunStore
from repro.runtime import (RetryPolicy, SortSupervisor, StageFailure,
                           StageFailureInjector)

oracle = distributed_chunked_sort_lex(keys)
td = tempfile.mkdtemp()
store = RunStore(td)
inj = StageFailureInjector(fail_at={"ingest_chunk": {2, 3, 4}})
sup = SortSupervisor(policy=RetryPolicy(max_retries=2), injector=inj)
try:
    distributed_chunked_sort_lex(keys, store=store, supervisor=sup)
    raise SystemExit("expected StageFailure")
except StageFailure:
    pass
assert store.completed() == [0, 1]

launches = []
real = ingest_mod.sorted_run
with mock.patch.object(ingest_mod, "sorted_run",
                       lambda k, **kw: launches.append(1) or real(k, **kw)):
    run = distributed_chunked_sort_lex(keys, store=store, validate="full")
assert_runs_equal(run, oracle)
assert len(launches) == 6  # chunks 0-1 loaded, 2-7 launched
assert store.completed() == list(range(8))

with mock.patch.object(ingest_mod, "sorted_run",
                       lambda k, **kw: launches.append(1) or real(k, **kw)):
    run2 = distributed_chunked_sort_lex(keys, store=store, validate="full")
assert_runs_equal(run2, oracle)
assert len(launches) == 6  # pure load, zero new launches
print("RESUME_OK")
""")
    assert "RESUME_OK" in out


# ---------------------------------------------------------------------------
# in-process degenerate cases (one local device)
# ---------------------------------------------------------------------------

def _words(n, seed, max_len=8):
    rng = np.random.default_rng(seed)
    alpha = list("abcdefgh")
    return ["".join(rng.choice(alpha, l))
            for l in rng.integers(0, max_len + 1, n)]


def test_single_device_degenerate_equals_pipeline():
    words = _words(150, 1)
    keys = np.asarray(pack_words(words))
    run = distributed_chunked_sort_lex(keys, devices=[jax.devices()[0]],
                                       validate="full")
    oracle = chunked_sort_packed(jnp.asarray(keys), chunk_size=150)
    np.testing.assert_array_equal(np.asarray(run.keys),
                                  np.asarray(oracle.keys))
    np.testing.assert_array_equal(np.asarray(run.lengths),
                                  np.asarray(oracle.lengths))


def test_empty_input_and_bad_args():
    empty = np.zeros((0, 2), np.uint32)
    run = distributed_chunked_sort_lex(empty)
    assert run.keys.shape[0] == 0 and run.lengths.shape[0] == 0
    keys = np.asarray(pack_words(_words(20, 2)))
    with pytest.raises(ValueError, match="validate"):
        distributed_chunked_sort_lex(keys, validate="bogus")
    with pytest.raises(ValueError, match="on_overflow"):
        distributed_chunked_sort_lex(keys, on_overflow="bogus")


def test_kill_between_exchange_and_combine_resumes_shard_granular():
    """A job killed mid streaming-combine (after the exchange, two
    destinations landed) must resume with ZERO ingest launches — every
    per-device run reloads from the run store and the exchange replays as a
    pure function of them — and re-merge only the destinations whose shards
    never landed. A second resume over the fully landed stores merges
    nothing at all. Output bit-identical throughout."""
    out = _run_multidev(_COMMON + """
import tempfile
from unittest import mock
import repro.pipeline.ingest as ingest_mod
import repro.pipeline.merge as merge_mod
from repro.pipeline import RunStore, ShardStore
from repro.runtime import (ProcessKilled, RetryPolicy, SortSupervisor,
                           StageFailureInjector)

oracle = distributed_chunked_sort_lex(keys)
run_store = RunStore(tempfile.mkdtemp())
shard_store = ShardStore(tempfile.mkdtemp())

inj = StageFailureInjector(kill_at={"streaming_combine": {2}})
sup = SortSupervisor(policy=RetryPolicy(max_retries=2), injector=inj)
try:
    distributed_chunked_sort_lex(keys, store=run_store,
                                 shard_store=shard_store, supervisor=sup)
    raise SystemExit("expected ProcessKilled")
except ProcessKilled as e:
    assert e.stage == "streaming_combine"
assert run_store.completed() == list(range(8))   # ingest fully landed
assert shard_store.completed() == [0, 1]         # killed during dest 2

launches, real_ingest = [], ingest_mod.sorted_run
real_merge = merge_mod.merge_runs
with mock.patch.object(ingest_mod, "sorted_run",
                       lambda k, **kw: launches.append(1)
                       or real_ingest(k, **kw)), \
     mock.patch.object(merge_mod, "merge_runs",
                       side_effect=real_merge) as merges:
    res = distributed_chunked_sort_lex(keys, store=run_store,
                                       shard_store=shard_store,
                                       validate="full")
assert len(launches) == 0       # exchange replayed from reloaded runs
assert merges.call_count == 6   # only destinations 2-7 re-merged
assert shard_store.completed() == list(range(8))
assert_runs_equal(res.to_run(validate="full"), oracle)

with mock.patch.object(merge_mod, "merge_runs",
                       side_effect=real_merge) as merges2:
    res2 = distributed_chunked_sort_lex(keys, store=run_store,
                                        shard_store=shard_store,
                                        validate="full")
assert merges2.call_count == 0  # double resume: pure shard reload
assert_runs_equal(res2.to_run(), oracle)
print("KILL_RESUME_OK")
""")
    assert "KILL_RESUME_OK" in out


def test_mesh_shard_spill_bit_identical():
    """8-device spill mode (``gather=False``): the sharded result's
    materialisation equals the gathered oracle bit-for-bit, with one shard
    per destination and the full metadata gate green."""
    out = _run_multidev(_COMMON + """
import tempfile
from repro.pipeline import ShardedRun, ShardStore

oracle = distributed_chunked_sort_lex(keys, validate="full")
sharded = distributed_chunked_sort_lex(
    keys, shard_store=ShardStore(tempfile.mkdtemp()), validate="full")
assert isinstance(sharded, ShardedRun)
assert len(sharded.manifests) == 8
assert sharded.count == 509
assert_runs_equal(sharded.to_run(validate="full"), oracle)
print("SPILL_MESH_OK")
""")
    assert "SPILL_MESH_OK" in out


def test_mesh_speculative_combine_bit_identical():
    """Speculative re-execution on the mesh: a straggling combine
    destination (injected fire-once slowness) gets a backup replica; the
    digest-confirmed winner keeps the output bit-identical."""
    out = _run_multidev(_COMMON + """
from repro.runtime import (SortSupervisor, SpeculationPolicy,
                           StageFailureInjector, StragglerMonitor)

oracle = distributed_chunked_sort_lex(keys)
mon = StragglerMonitor(warmup=3, min_ratio=3.0)
inj = StageFailureInjector(slow_at={"streaming_combine": {5: 2.0}})
sup = SortSupervisor(
    injector=inj,
    speculation=SpeculationPolicy(monitor=mon, min_wait=0.05))
run = distributed_chunked_sort_lex(keys, supervisor=sup, validate="full")
assert_runs_equal(run, oracle)
assert ("streaming_combine", 5, "slow") in inj.fired
actions = [e.action for e in sup.events]
assert "speculate" in actions, actions
assert "speculation_confirmed" in actions, actions
print("SPECULATE_OK")
""")
    assert "SPECULATE_OK" in out


# ---------------------------------------------------------------------------
# fixed shapes: every program of the mesh path follows from n and the device
# count alone (4 fake devices, as the four chips of one host)
# ---------------------------------------------------------------------------

_MESH4 = """
import logging, sys
import numpy as np, jax
sys.path.insert(0, {bench!r})
from compile_log import CompileLog
from repro.core.distributed import distributed_chunked_sort_lex
from repro.core.packing import pack_words, unpack_words

assert len(jax.devices()) == 4

def words(n, seed, hot=0.0):
    rng = np.random.default_rng(seed)
    ws = ["".join(rng.choice(list("abcdefgh"), l))
          for l in rng.integers(0, 9, n)]
    return ["abc" if rng.random() < hot else w for w in ws]

def oracle(ws):
    return sorted(ws, key=lambda w: (len(w.encode()), w.encode()))

def check(run, ws):
    got = unpack_words(np.asarray(run.keys))
    assert got == oracle(ws)
    assert np.asarray(run.lengths).tolist() == [len(w) for w in got]
"""


def _bench_dir():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "bench")


def test_mesh_corpora_of_one_size_share_every_program():
    """Two corpora of the same n with different keys each come back in
    exact shortlex order, and the second compiles nothing: the exchange's
    slots, the combines and the gather take their shapes from n and the
    device count, never from where the splitters cut the keys."""
    out = _run_multidev(_MESH4.format(bench=_bench_dir()) + """
first, second = words(1000, 3), words(1000, 4)
with CompileLog() as warm:
    check(distributed_chunked_sort_lex(pack_words(first)), first)
with CompileLog() as again:
    check(distributed_chunked_sort_lex(pack_words(second)), second)
assert warm.compiles() > 0
assert again.compiles() == 0, str(again)
print("FIXED_SHAPES_OK")
""", devices=4)
    assert "FIXED_SHAPES_OK" in out


def test_mesh_hot_key_past_its_slot_keeps_every_row():
    """A hot key pushes one destination's pieces past the default slot (128
    rows for chunks of 256 on 4 devices): the slot doubles, with a warning,
    and the default call comes back bit-identical. Against a destination
    capacity below the hot destination, 'raise' raises, 'retry' comes back
    bit-identical and 'clip' keeps each destination's capacity smallest
    rows, in order."""
    out = _run_multidev(_MESH4.format(bench=_bench_dir()) + """
from repro.core.distributed import _slot_rows
from repro.runtime import CapacityOverflow

assert _slot_rows(1024, 4) == 128
hot = words(1024, 5, hot=0.7)
keys = pack_words(hot)
grew = []
handler = logging.Handler()
handler.emit = lambda record: grew.append(record.getMessage())
logging.getLogger("repro.core").addHandler(handler)
run = distributed_chunked_sort_lex(keys)
check(run, hot)
assert any("outgrows its slot of 128" in m for m in grew), grew

n_hot = hot.count("abc")
try:
    distributed_chunked_sort_lex(keys, capacity=400, on_overflow="raise")
    raise SystemExit("expected CapacityOverflow")
except CapacityOverflow as e:
    assert e.capacity == 400 and e.required >= n_hot
check(distributed_chunked_sort_lex(keys, capacity=400, on_overflow="retry",
                                   validate="full"), hot)

clip = distributed_chunked_sort_lex(keys, capacity=400, on_overflow="clip",
                                    validate="cheap")
got, want = unpack_words(np.asarray(clip.keys)), oracle(hot)
# every copy of the hot word lands on one destination, the only one past
# 400 rows: the output is the oracle less that destination's largest rows,
# one contiguous block of at least n_hot - 400
drop = len(want) - len(got)
assert drop >= n_hot - 400
i = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
         len(got))
assert got[:i] == want[:i] and got[i:] == want[i + drop:]
assert 0 < got.count("abc") <= 400
print("HOT_KEY_OK")
""", devices=4)
    assert "HOT_KEY_OK" in out


@pytest.mark.parametrize("engine", ["kway", "kway_kernel"])
def test_merge_runs_at_a_fixed_shape_equals_the_exact_merge(engine):
    """One chip's ``merge_runs`` over runs padded to one fixed shape, with
    their real counts, gives the merge of the exact runs first: padding of
    any value (here random, below real rows too) never enters, and a real
    row equal to the all-ones tuple still precedes every padding row."""
    from repro.pipeline.merge import merge_runs
    rng = np.random.default_rng(7)
    top = np.uint32(0xFFFFFFFF)
    slot, counts = 256, [90, 0, 130, 256]
    runs = []
    for c in counts:
        a = rng.integers(0, 6, (c, 2)).astype(np.uint32)
        a[rng.random(c) < 0.1] = top          # real rows at the maximum
        a = a[np.lexsort((a[:, 1], a[:, 0]))]
        runs.append((a[:, 0], a[:, 1]))
    padded = [tuple(np.concatenate([x, rng.integers(
        0, 2**32, slot - len(x), dtype=np.uint32)]) for x in r)
        for r in runs]
    exact = merge_runs([tuple(jnp.asarray(x) for x in r) for r in runs
                        if len(r[0])], engine=engine)
    fixed = merge_runs([tuple(jnp.asarray(x) for x in r) for r in padded],
                       engine=engine, counts=np.asarray(counts, np.int32))
    total = sum(counts)
    both = np.concatenate([np.stack(r, axis=1) for r in runs])
    want = both[np.lexsort((both[:, 1], both[:, 0]))]
    for i in range(2):
        assert fixed[i].shape == (slot * len(counts),)
        np.testing.assert_array_equal(np.asarray(fixed[i])[:total],
                                      np.asarray(exact[i]))
        np.testing.assert_array_equal(np.asarray(exact[i]), want[:, i])
    with pytest.raises(ValueError, match="k-way"):
        merge_runs(padded, engine="tournament", counts=counts)
