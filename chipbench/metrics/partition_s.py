"""Host seconds of ``session.matrix()``: ``core/partition.partition_csr``
and ``core/spmv.shard_matrix`` (layer: partition / setup). Moves
``setup_s``."""


def read(ctx):
    return ctx.spans.get("partition_s")
