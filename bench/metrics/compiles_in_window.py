"""XLA backend compiles (or persistent-cache loads) JAX reported between
window start and end (``jax.monitoring``)."""


def read(run):
    return float(sum(1 for t in run.compile_times
                     if run.t_start <= t <= run.t_end))
