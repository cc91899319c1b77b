#!/usr/bin/env python3
"""Record a small profiler trace on the chip and describe its layout.

    python3 bench/tools/record_trace.py OUT_DIR

Runs the fused KD loss (forward and backward) and a jitted matmul inside a
``bench.window`` host span, writes the ``.xplane.pb`` to OUT_DIR/trace.xplane.pb
and prints every plane and line with a sample of event names and stats. The
recorded file is the fixture of ``bench/tests/test_trace_reduce.py``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    os.makedirs(out, exist_ok=True)
    R, V = 64, 400
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    s = jax.random.normal(ks[0], (R, V), jnp.float32)
    t = jax.random.normal(ks[1], (R, V), jnp.float32)
    lab = jax.random.randint(ks[2], (R,), 0, V, jnp.int32)
    kd = jax.jit(jax.value_and_grad(
        lambda s, t: jnp.mean(ops.kd_loss_rows(s, t, lab, 0.5))))
    mm = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready((kd(s, t), mm(x)))          # compile outside
    raw = os.path.join(out, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.engine"):
                out_kd = kd(s, t)
                y = mm(x)
            with jax.profiler.TraceAnnotation("bench.hook"):
                jax.block_until_ready((out_kd, y))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(raw, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(out, "trace.xplane.pb"))
    shutil.rmtree(raw)
    pd = jax.profiler.ProfileData.from_file(os.path.join(out,
                                                         "trace.xplane.pb"))
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("    EV", repr(e.name), e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:8]) if e.stats else {})
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    raise SystemExit(main(sys.argv[1]))
