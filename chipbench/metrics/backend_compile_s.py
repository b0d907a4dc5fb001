"""Host seconds of the solve's backend compile, or of its load from the
persistent compilation cache, during ``SolverHandle.warm``, as the program
counts them itself (``warm_backend_compile_seconds`` of
``repro.obs.metrics.REGISTRY``, from JAX's monitoring events; layer:
compile). Moves ``setup_s``. Silent where the program keeps no such
counter."""

from chipbench import counters


def read(ctx):
    return counters.value("warm_backend_compile_seconds")
