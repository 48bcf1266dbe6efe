"""``chip_smoke.py``'s phases at a tiny size on the CPU, in interpret mode,
held to the same NumPy shortlex reference the script checks on the chip;
and the script itself refusing to run off a TPU."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.core.packing import pack_words

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_make_words_matches_the_packer():
    keys, lengths = chip_smoke.make_words(2000, seed=3)
    assert keys.shape == (2000, 4) and keys.dtype == np.uint32
    assert lengths.min() >= 1 and lengths.max() <= 15
    b = keys[..., None] >> np.array([24, 16, 8, 0], np.uint32)
    chars = (b & 0xFF).astype(np.uint8).reshape(2000, 16)
    words = [bytes(c[:n]).decode() for c, n in zip(chars, lengths)]
    assert all(w.isalpha() and w.islower() and len(w) == n
               for w, n in zip(words, lengths))
    np.testing.assert_array_equal(keys, pack_words(words, width=16))
    assert len(set(words)) < len(words)  # duplicates occur
    again, _ = chip_smoke.make_words(2000, seed=3)
    np.testing.assert_array_equal(keys, again)


def test_reference_is_shortlex():
    keys, lengths = chip_smoke.make_words(500, seed=1)
    order = chip_smoke.reference_order(keys, lengths)
    rows = [(int(lengths[i]), *map(int, keys[i])) for i in order]
    assert rows == sorted(rows)


def test_check_result_catches_a_swap():
    keys, lengths = chip_smoke.make_words(300, seed=2)
    order = chip_smoke.reference_order(keys, lengths)
    chip_smoke.check_result(lengths[order], keys[order], keys, lengths,
                            order, "ok")
    bad = keys[order].copy()
    bad[[0, -1]] = bad[[-1, 0]]
    with pytest.raises(AssertionError, match="differ"):
        chip_smoke.check_result(lengths[order], bad, keys, lengths, order,
                                "swapped")


def test_chunked_phase_matches_reference():
    keys, lengths = chip_smoke.make_words(1500, seed=4)
    order = chip_smoke.reference_order(keys, lengths)
    lines = []
    merged = chip_smoke.run_chunked(keys, 512, log=lines.append)
    chip_smoke.check_result(merged.lengths, merged.keys, keys, lengths,
                            order, "chunked_sort_packed")
    phases = [l.split(":")[0] for l in lines if l.startswith("phase")]
    assert phases == ["phase chunk_sort", "phase chunked_sort_packed",
                      "phase combine"]
    # the timed chunks run the program chunked_sort_packed runs for a full
    # chunk, so it compiles there only for the short tail chunk
    assert lines[0].count("_fused_sort_packed") == 1
    assert lines[1].count("_fused_sort_packed") == 1


def test_compile_log_names_each_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def smoke_probe(x):
        return x * 3 + 1

    with chip_smoke.CompileLog() as first:
        smoke_probe(jnp.arange(7)).block_until_ready()
    with chip_smoke.CompileLog() as again:
        smoke_probe(jnp.arange(7)).block_until_ready()
    assert [n for n in first.seconds if "smoke_probe" in n]
    assert first.total() > 0 and first.hits == 0
    assert again.total() == 0 and "nothing compiled" in str(again)


def test_mesh_phase_on_four_fake_devices():
    """The ``--chips 4`` phase on four fake CPU devices (a child process:
    the device count is fixed when JAX starts)."""
    script = """
import sys
sys.path.insert(0, {repo!r})
import jax, chip_smoke
assert len(jax.devices()) == 4
keys, lengths = chip_smoke.make_words(700, seed=5)
order = chip_smoke.reference_order(keys, lengths)
chip_smoke.run_mesh(keys, lengths, order, jax.devices())
print("MESH-OK")
""".format(repo=os.path.abspath(REPO))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.abspath(REPO), "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH-OK" in out.stdout


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo", "script-alone"])
def test_refuses_to_run_off_the_chip(tmp_path, alone):
    """On the CPU, and beside no repo at all, the script exits non-zero
    and prints no result."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = str(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
