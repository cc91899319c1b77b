#!/usr/bin/env python3
"""Compile each cell's timed programs for a described TPU v5e (no chip
needed) and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py [kd|sync|async|ref]...

kd: the KD epoch (teacher resnet3d-34, student resnet3d-18, 8 steps of 64
clips at 8x112x112, stack donated) as ``DistillEngine.epoch`` compiles it.
sync: the padded sync round (4 clients, H_max 3, batch 8, params donated).
async: one client's run for H = 1, 2, 3 and the kickoff's padded batch of
4 clients. ref: the references' blocks at ``highest`` precision. Nothing
runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def _report(name, compiled, t0):
    ma = compiled.memory_analysis()
    gib = 1 << 30
    print(json.dumps({
        "program": name, "compile_s": round(time.perf_counter() - t0, 1),
        "temp_gib": ma.temp_size_in_bytes / gib,
        "argument_gib": ma.argument_size_in_bytes / gib,
        "output_gib": ma.output_size_in_bytes / gib,
        "alias_gib": ma.alias_size_in_bytes / gib,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}),
        flush=True)


def main(which) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core import distill, fed_engine
    from repro.models import registry
    from repro.optim import trainable_mask
    from repro.types import DistillConfig, FedConfig

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    def clips(*lead):
        return {"clips": jax.ShapeDtypeStruct(lead + (8, 112, 112, 3),
                                              jnp.float32, sharding=one),
                "labels": jax.ShapeDtypeStruct(lead, jnp.int32,
                                               sharding=one)}

    key = jax.random.PRNGKey(0)
    r18, r34 = get_config("resnet3d-18"), get_config("resnet3d-34")
    p18 = sds(jax.eval_shape(lambda: registry.init_params(key, r18)))
    p34 = sds(jax.eval_shape(lambda: registry.init_params(key, r34)))

    if "kd" in which:
        eng = distill.DistillEngine(r34, r18, DistillConfig())
        opt = sds(jax.eval_shape(eng.opt.init, p18))
        t0 = time.perf_counter()
        c = jax.jit(eng._epoch, donate_argnums=(3,)).lower(
            p34, p18, opt, clips(8, 64)).compile()
        _report("kd_epoch_8x64", c, t0)

    fed = FedConfig()
    run = fed_engine.ClientRun(r18, fed)
    mask = sds(jax.eval_shape(lambda p: trainable_mask(p, "all"), p18))
    if "sync" in which:
        rnd = fed_engine.SyncRound(r18, fed)
        t0 = time.perf_counter()
        c = jax.jit(rnd._rnd_padded, donate_argnums=(0, 1)).lower(
            p18, clips(4, 3, 8),
            jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one),
            mask, (), ()).compile()
        _report("sync_round_4x3x8", c, t0)
    if "async" in which:
        for h in (1, 2, 3):
            t0 = time.perf_counter()
            c = jax.jit(run._run, donate_argnums=(1,)).lower(
                p18, clips(h, 8), mask, (), ()).compile()
            _report(f"client_run_H{h}_b8", c, t0)
        t0 = time.perf_counter()
        c = jax.jit(run._run_padded_batch, donate_argnums=(1,)).lower(
            p18, clips(4, 3, 8),
            jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one),
            mask, (), ()).compile()
        _report("kickoff_4x3x8", c, t0)
    if "ref" in which:
        from reference import fed as rfed, kd as rkd
        hi = jax.lax.Precision.HIGHEST
        t0 = time.perf_counter()
        c = rkd._block_grad.lower(
            p34, p18, clips(32)["clips"], alpha=0.5, temperature=1.0,
            batch=64, precision=hi).compile()
        _report("ref_kd_block_32", c, t0)
        t0 = time.perf_counter()
        cl = clips(8)
        c = rfed._local_step.lower(
            p18, p18, p18, cl["clips"], cl["labels"], lr=1e-3, momentum=0.9,
            theta=0.01, precision=hi, rows=None).compile()
        _report("ref_local_step_b8", c, t0)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:] or ["kd", "sync", "async", "ref"]))
