"""Host seconds in ``core/partition.partition_csr``, as the program
counts them itself (``setup_partition_csr_seconds`` of
``repro.obs.metrics.REGISTRY``, added by ``SolverSession.matrix``; layer:
partition / setup). Moves ``setup_s``. Silent where the program keeps no
such counter."""

from chipbench import counters


def read(ctx):
    return counters.value("setup_partition_csr_seconds")
