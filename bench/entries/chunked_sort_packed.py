"""One chip: ``pipeline.chunked_sort_packed`` with the library's defaults
(Pallas in-bucket sort, ``merge_engine='auto'``, ``validate='off'``, no
store) and the cell's ``chunk_size``."""


def make(cell: dict, devices: list):
    from repro.pipeline import chunked_sort_packed

    def job(keys):
        run = chunked_sort_packed(keys, chunk_size=cell["chunk_size"])
        return run.lengths, run.keys

    return job
