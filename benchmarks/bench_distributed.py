"""Distributed engine sweep: engines x P in {2, 4, 8} devices x merge
strategies, key-only and 4-lane lex, against the single-device jnp.sort
baseline.

Runs in this process over ``jax.devices()``: every P up to the device count
is swept. On a TPU host that is the chips; for the CPU rehearsal on fake
devices, set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and
``JAX_PLATFORMS=cpu`` before starting the benchmark. The headline record is
the sample-vs-odd-even crossover: odd_even pays P merge rounds and O(P*B)
ICI bytes per device, sample one splitter exchange of O(B) bytes, so the
modeled byte crossover sits at P ~ 3 (``choose_engine``'s boundary) and the
measured ratio climbs toward / past 1 with P. On fake CPU devices the
collectives carry millisecond-level rendezvous jitter that flatters
odd_even's ppermute, so the measured key-only ratio trails the model there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import distributed_sort, distributed_sort_lex
from repro.parallel.compat import AxisType, mesh_from_devices

from .common import emit, rng as bench_rng, timeit


def _mesh(p):
    return mesh_from_devices(np.array(jax.devices()[:p]), ("d",),
                             axis_types=(AxisType.Auto,))


def main():
    ps = [p for p in (2, 4, 8) if p <= len(jax.devices())]
    if not ps:
        raise RuntimeError(
            f"bench_distributed needs >= 2 devices, found "
            f"{len(jax.devices())} (fake CPU devices: XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu)")
    rng = bench_rng("bench_distributed", 0)

    # --- small-block regime: every merge strategy (take is O(B^2), so only
    # here)
    n = 1 << 12
    x = jnp.asarray(rng.integers(0, 2**31, n).astype(np.int32))
    for p in ps:
        mesh = _mesh(p)
        for merge in ("resort", "bitonic", "take"):
            t = timeit(lambda v: distributed_sort(v, mesh, axis="d",
                                                  engine="odd_even",
                                                  merge=merge), x, iters=3)
            emit("distributed/odd_even-%s/P%d/n%d" % (merge, p, n), t * 1e6,
                 "rounds=%d" % p)
        t = timeit(lambda v: distributed_sort(v, mesh, axis="d",
                                              engine="sample"), x, iters=3)
        emit("distributed/sample/P%d/n%d" % (p, n), t * 1e6, "rounds=1")

    # --- key-only + 4-lane lex crossover sweep
    n = 1 << 15
    x = jnp.asarray(rng.integers(0, 2**31, n).astype(np.int32))
    lanes = [jnp.asarray(rng.integers(0, 2**31, n).astype(np.uint32))
             for _ in range(4)]
    t_base = timeit(jax.jit(jnp.sort), x, iters=5)
    emit("distributed/jnp_sort_1dev/n%d" % n, t_base * 1e6)
    ratios = {}
    for p in ps:
        mesh = _mesh(p)
        for kind in ("key", "lex4"):
            if kind == "key":
                oe = lambda v: distributed_sort(v, mesh, axis="d",
                                                engine="odd_even",
                                                merge="resort")
                sa = lambda v: distributed_sort(v, mesh, axis="d",
                                                engine="sample")
                args = (x,)
            else:
                oe = lambda *ls: distributed_sort_lex(list(ls), mesh,
                                                      axis="d",
                                                      engine="odd_even",
                                                      merge="resort")
                sa = lambda *ls: distributed_sort_lex(list(ls), mesh,
                                                      axis="d",
                                                      engine="sample")
                args = tuple(lanes)
            t_oe = timeit(oe, *args, iters=5)
            t_sa = timeit(sa, *args, iters=5)
            ratios[(kind, p)] = t_oe / t_sa
            emit("distributed/odd_even-resort-%s/P%d/n%d" % (kind, p, n),
                 t_oe * 1e6,
                 "rounds=%d;bytes_per_dev=%d" % (p, 2 * p * (n // p) * 4))
            emit("distributed/sample-%s/P%d/n%d" % (kind, p, n), t_sa * 1e6,
                 "rounds=1;bytes_per_dev=%d;vs_odd_even=%.2fx"
                 % (3 * (n // p) * 4, t_oe / t_sa))

    # --- the crossover record: modeled ICI bytes cross at P=3 (2PB vs 3B
    # -> choose_engine's P<=2 boundary); measured wall-clock ratios per P
    trend = ";".join("%s_P%d=%.2f" % (k, p, r)
                     for (k, p), r in sorted(ratios.items()))
    crossed = [p for (k, p), r in ratios.items() if r >= 1.0]
    emit("distributed/crossover/n%d" % n, 0.0,
         "model_bytes_cross_P=3;measured_ratio{%s};measured_cross_P=%s"
         % (trend, min(crossed) if crossed else ">%d" % ps[-1]))


if __name__ == "__main__":
    main()
