"""XLA's own backend-compile events inside the window (``jax.monitoring``
durations whose name has ``backend_compile``), not ``CompileStats``."""


def read(obs):
    return float(sum(
        1 for t, name in obs.compile_events
        if obs.in_window(t) and "backend_compile" in name
    ))
