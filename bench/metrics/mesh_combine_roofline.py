"""The mesh combines' share of one chip's HBM roofline, in percent:
``combine_roofline``'s reading, in the mesh cell. The layer must read each
sorted word with its length (20 B) and write it merged (20 B); over
``mesh_combine_ms``, the combines' device time summed over the chips. Rank
keys and slot padding are the implementation's own."""

import harness

read = harness.load_module("metrics", "combine_roofline").read
