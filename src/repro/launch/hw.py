"""Target-hardware constants (TPU v5e) used by the roofline analysis.

These numbers parameterize the *model* of the machine the dry-run compiles
for; they are not keyed by device kind and no measurement is divided by
them. Sources: assignment spec.
"""

PEAK_FLOPS_BF16 = 197e12     # per chip, bf16
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~)
HBM_BYTES = 16 * 1024**3     # 16 GiB per chip

# effective bytes moved per element of collective *output*, ring algorithms:
#   all-reduce = reduce-scatter + all-gather  -> ~2x payload over the slowest link
#   all-gather / reduce-scatter / all-to-all / collective-permute -> ~1x
COLLECTIVE_MULTIPLIER = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
