"""window_compiles.<kind>: XLA backend compiles inside the measured
window (JAX monitoring events, ``compile_log.py``); 0 when set-up warmed
every shape."""


def read(name, m):
    return float(m["window_compiles"])
