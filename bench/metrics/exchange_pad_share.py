"""What the mesh exchange's fixed shapes cost: the padded rows handed to
the destination combines that hold no word, over all rows handed to them,
``sum(slots - rows) / sum(slots)`` over the ``sort.run_exchange`` spans
that start inside the traced window."""

import mesh


def read(run):
    ex = mesh.window_spans(run, "run_exchange")
    if not ex or any("slots" not in s.attrs for s in ex):
        return None
    slots = sum(int(s.attrs["slots"]) for s in ex)
    rows = sum(int(s.attrs["rows"]) for s in ex)
    return (slots - rows) / slots if slots else None
