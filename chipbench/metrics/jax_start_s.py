"""Host seconds from the process's start until JAX has found the chips:
Python's imports of JAX and the program, and JAX's start on the TPU
(layer: device). Moves ``setup_s``."""


def read(ctx):
    return ctx.spans.get("jax_start_s")
