"""setup_compile_s: seconds of XLA backend compiles in set-up (JAX
monitoring events, ``compile_log.py``); near 0 when every program came
from the persistent compile cache."""


def read(name, m):
    return float(m["setup_compile_s"])
