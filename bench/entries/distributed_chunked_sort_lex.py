"""The chips of one host: ``core.distributed.distributed_chunked_sort_lex``
over the devices the harness hands over, with the library's defaults
(``merge_engine='auto'``, ``validate='off'``, no store, the result gathered
onto the first device). Each chip ingests one chunk, so the cell's
``chunk_size`` must be ``ceil(words_per_job / chips)``; any other is
refused. So is a program whose mesh path has no fixed-shape gather (no
``sort.gather`` span): its shapes follow from the keys, and it would
compile inside the window."""

import json
import os

import harness


def make(cell: dict, devices: list):
    from repro.core.distributed import distributed_chunked_sort_lex
    from repro.runtime.trace import SPANS

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", cell["config"] + ".json")
    with open(path) as f:
        words = json.load(f)["words_per_job"]
    chunk = -(-words // len(devices))
    if cell["chunk_size"] != chunk:
        raise harness.Refused(
            f"chunk_size {cell['chunk_size']}: the mesh entry ingests one "
            f"chunk per chip, ceil({words} / {len(devices)}) = {chunk}")
    if "gather" not in SPANS:
        raise harness.Refused("the program's mesh path has no fixed-shape "
                              "gather (no sort.gather span)")

    def job(keys):
        run = distributed_chunked_sort_lex(keys, devices=devices)
        return run.lengths, run.keys

    return job
