"""Share of the window JAX spent tracing, lowering and compiling or reading
its persistent cache, from ``jax.monitoring``, in %."""


def read(window):
    return 100.0 * window.compile["seconds"] / window.window_s
