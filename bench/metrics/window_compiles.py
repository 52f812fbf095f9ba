"""JAX compilations that ended inside the window, by the program's
``jax_compiles_total`` (``repro.obs.metrics.COMPILES``): set-up warms
every shape, so it should read 0 (program counter).  Silent where the
program does not count compilations."""


def read(r):
    from repro.obs import metrics
    watch = getattr(metrics, "COMPILES", None)
    if watch is None or not watch.installed:
        return None
    return len(watch.between(r.window.start, r.window.end))
