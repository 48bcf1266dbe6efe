"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached, at the sizes the pipeline runs them (``chip_smoke.py``'s chunk of
2^20 words). Nothing runs: a pass says the TPU compiler accepts the kernel
(its lowering, tiling, VMEM and SMEM), which interpret mode cannot show.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bitonic_kernel import bitonic_rows_lex_pallas
from repro.kernels.distribute_kernel import distribute_rows_pallas
from repro.kernels.kway_kernel import kway_kernel_call, merge_runs_kway_pallas
from repro.kernels.merge_kernel import merge_adjacent_lex_pallas
from repro.kernels.oets_kernel import oets_rows_lex_pallas
from repro.kernels.partition_kernel import partition_rows_pallas
from repro.kernels.runmerge_kernel import merge_runs_lex_pallas

CHUNK = 1 << 20          # chip_smoke.py's chunk size C
LANES = 4                # uint32 lanes of a 15-byte word
BUCKETS = 4 * LANES + 1  # one bucket per byte length 0..16
RUNS = 16                # 2^24 words / C
# k-way arity on the pipeline's combine: 5 compare lanes + (length, 4 keys)
KWAY_ARRAYS = 10
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_distribute_full_chunk(one_chip):
    _compile(one_chip, lambda k: distribute_rows_pallas(
        k, n_valid=CHUNK, num_buckets=BUCKETS, interpret=False),
        ((LANES, CHUNK), U32))


def test_bitonic_1024_lanes(one_chip):
    _compile(one_chip, lambda a, b: bitonic_rows_lex_pallas(
        a, b, interpret=False), ((8, 1024), U32), ((8, 1024), U32))


def test_oets_one_tile(one_chip):
    _compile(one_chip, lambda a, b: oets_rows_lex_pallas(
        a, b, interpret=False), ((8, 128), U32), ((8, 128), U32))


def test_partition(one_chip):
    _compile(one_chip, lambda x, s: partition_rows_pallas(
        x, s, n_splitters=7, n_buckets=8, interpret=False),
        ((8, 1024), I32), ((1, 128), I32))


def test_segmented_sort_full_chunk(one_chip):
    """The fused program's bucket tensor at its real capacity (every bucket
    may hold the whole chunk): distribute's output sorted by the blocksort
    tier — bitonic blocks and cross-block merge rounds."""
    _compile(one_chip, lambda k, c: ops.segmented_sort(k, c, interpret=False),
             ((BUCKETS, CHUNK, LANES), U32), ((BUCKETS,), I32))


@pytest.mark.parametrize("block,n_arrays", [(1 << 15, 1), (1 << 13, 4)])
def test_blocksort_largest_blocks(one_chip, block, n_arrays):
    """``core/blocksort.default_block_size``'s cap: 32Ki lanes key-only,
    halved per pow2 tuple width — the merge kernel's VMEM high-water mark."""
    shapes = [((24, 1 << 20), U32)] * n_arrays
    _compile(one_chip, lambda *a: merge_adjacent_lex_pallas(
        *a, block=block, interpret=False), *shapes)
    _compile(one_chip, lambda *a: bitonic_rows_lex_pallas(
        *a, interpret=False), *[((2176, block), U32)] * n_arrays)


def test_runmerge(one_chip):
    n = 1 << 16
    _compile(one_chip, lambda a0, a1, b0, b1: merge_runs_lex_pallas(
        [a0, a1], [b0, b1], interpret=False), *[((n,), U32)] * 4)


def test_kway_kernel_at_chunk_runs(one_chip):
    """The streaming k-way kernel launch as the one-chip combine makes it:
    16 runs of C rows, 10 arrays each, 2^16 output blocks (whose segment
    table overflows SMEM if prefetched whole)."""
    block = 256
    nblocks = RUNS * CHUNK // block
    cols = -(-(nblocks + 1) // 128) * 128
    flat_len = RUNS * (CHUNK + block + 128)
    _compile(one_chip, lambda s, *f: kway_kernel_call(
        s, *f, nblocks=nblocks, block=block, interpret=False),
        ((RUNS, cols), I32), *[((1, flat_len), U32)] * KWAY_ARRAYS)


def test_kway_kernel_keeps_its_op_name_inside_its_scope(one_chip):
    """The compiled k-way program names its Pallas custom call
    ``_kway_merge_jit.N``, the operation name device traces show for the
    kernel, while its metadata carries the ``kway_kernel`` scope."""
    args = [jax.ShapeDtypeStruct((n,), U32, sharding=one_chip)
            for n in (4096, 4096, 1328) for _ in range(5)]
    hlo = jax.jit(lambda *a: merge_runs_kway_pallas(
        [a[i:i + 5] for i in range(0, 15, 5)], n_cmp=4,
        interpret=False)).lower(*args).compile().as_text()
    calls = re.findall(r'%(\S+) = .*custom-call\(.*custom_call_target='
                       r'"tpu_custom_call".*op_name="([^"]*)"', hlo)
    assert [(re.sub(r"\.\d+$", "", name), "/kway_kernel/" in path)
            for name, path in calls] == [("_kway_merge_jit", True)]


_FLOAT_MERGES = {
    "runmerge": lambda a, b: merge_runs_lex_pallas([a], [b],
                                                   interpret=False)[0],
    "kway_kernel": lambda a, b: merge_runs_kway_pallas(
        [(a,), (b,)], block=128, interpret=False)[0],
    "kway_take": lambda a, b: ops.merge_runs_lex([(a,), (b,)],
                                                 engine="take")[0],
}


@pytest.mark.parametrize("name", sorted(_FLOAT_MERGES))
def test_float_merge_keeps_bits(one_chip, name):
    """Float32 merges compile, and with no float ``maximum`` in them: XLA
    on TPU may lower a float concatenate to padded operands joined by
    ``maximum``, which canonicalises NaN payloads."""
    args = [jax.ShapeDtypeStruct((300,), jnp.float32, sharding=one_chip)] * 2
    hlo = jax.jit(_FLOAT_MERGES[name]).lower(*args).compile().as_text()
    assert not re.search(r"f32\[[0-9,]*\][^ ]* maximum\(", hlo)
