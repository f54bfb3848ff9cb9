"""Real non-zeros over the slab slots of the packed matrix, in percent
(program counters: ``SparseTensor.nnz`` and the slab arrays' shape)."""


def read(record):
    c = record["counters"]
    if not c.get("slab_slots"):
        return None
    return 100.0 * c["nnz"] / c["slab_slots"]
