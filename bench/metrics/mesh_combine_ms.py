"""Device milliseconds per job of the mesh path's per-destination k-way
combines (``_kway_merge_jit``, or the jnp ``take`` tier), summed over the
chips: ``combine_ms``'s reading, in the mesh cell."""

import harness

read = harness.load_module("metrics", "combine_ms").read
