"""Backend compiles inside the measured window, from JAX's own
``/jax/core/compile/backend_compile_duration`` events.  Should be 0."""
UNIT = "count"


def read(view):
    return view.window.compiles
