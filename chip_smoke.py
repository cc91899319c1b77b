#!/usr/bin/env python3
"""Bring-up check of the paper's main path on a TPU, at published widths.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the sharded sync round on four chips

One chip runs three phases, through the entry points a user calls:

1. kd: stage-1 distillation, resnet3d-34 -> resnet3d-18 (random weights
   from ``--seed``) on 8x112x112 clips, batch 16, via
   ``core.distill.run_chain``. The compiled KD epoch must hold the fused
   Pallas loss (``tpu_custom_call``), and the kernel's loss and gradient
   must match ``kernels.ref.kd_loss_ref`` on the chip.
2. finetune: stage-2 federated fine-tuning of the distilled student over 4
   Jetson-profile clients (batch 8, H in [1, 3]): 2 sync rounds, then a
   few async receives, both on the scan engine. Losses must be finite.
3. parity: one padded sync round on the scan engine against the per-client
   loop oracle, at ``highest`` matmul precision.

``--chips 4`` runs only the sharded round: 8 clients through the ``shard``
engine on a ('clients',) mesh and the ``hier`` engine on an
('edge', 'clients') mesh, each compared with the scan engine on one chip.

Every phase prints its numbers on its own line. The last line of stdout,
printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

KD_BATCH = 16        # > 8 rows: the KD kernel's multi-row-block path
KD_STEPS = 3
FT_CLIENTS = 4
FT_BATCH = 8
SYNC_ROUNDS = 2
ASYNC_RECEIVES = 3
PARITY_H = (3, 1, 2, 3)               # heterogeneous: the padded round
SHARD_H = (3, 1, 2, 3, 2, 1, 3, 2)      # 8 clients
# the one-chip scan reference holds all 8 clients' training state at once:
# compiled for a described v5e, the round needs 15.6 GiB of temporaries at
# batch 8 (too much for its 16 GiB) and 11.9 GiB at batch 4
SHARD_BATCH = 4
# KD kernel vs its jnp reference: both run float32 math, only the order of
# the vocab reductions differs (online logsumexp over tiles)
KD_RTOL = 1e-4
# scan/shard/hier round vs its reference at highest precision: float32
# accumulation order differs (vmapped vs per-client convolutions, psum)
# over at most 3 SGD steps of lr 1e-3
PARAM_ATOL = 1e-4


class CompileLog:
    """Counts XLA compiles and persistent-cache hits from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.events = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.events[event] += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events["compiles"] += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"compiles": self.events["compiles"],
                "cache_hits":
                    self.events["/jax/compilation_cache/cache_hits"],
                "compile_s": self.compile_s}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


def _report(phase: str, **numbers):
    print(f"[{phase}] " + json.dumps(numbers), flush=True)


def _max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _all_finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree_util.tree_leaves(tree))


def _check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def kd_kernel_parity(R: int, V: int, alpha: float, temperature: float,
                     seed: int) -> dict:
    """The fused KD loss against its jnp reference on this backend: the
    per-row loss and the gradient to both logit tensors."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    s = 3.0 * jax.random.normal(ks[0], (R, V), jnp.float32)
    t = 3.0 * jax.random.normal(ks[1], (R, V), jnp.float32)
    lab = jax.random.randint(ks[2], (R,), 0, V, jnp.int32)
    w = jax.random.uniform(ks[3], (R,), jnp.float32)

    def weighted(loss_rows):
        return lambda s, t: jnp.sum(w * loss_rows(s, t, lab, alpha,
                                                  temperature=temperature))

    kern = jax.jit(lambda s, t: (
        ops.kd_loss_rows(s, t, lab, alpha, temperature=temperature),
        jax.grad(weighted(ops.kd_loss_rows), argnums=(0, 1))(s, t)))
    refr = jax.jit(lambda s, t: (
        ref.kd_loss_ref(s, t, lab, alpha, temperature=temperature),
        jax.grad(weighted(ref.kd_loss_ref), argnums=(0, 1))(s, t)))
    (l_k, g_k), (l_r, g_r) = kern(s, t), refr(s, t)
    scale_l = float(jnp.max(jnp.abs(l_r)))
    scale_g = max(float(jnp.max(jnp.abs(g))) for g in g_r)
    return {"R": R, "V": V,
            "loss_max_rel_diff": _max_abs_diff(l_k, l_r) / scale_l,
            "grad_max_rel_diff": _max_abs_diff(g_k, g_r) / scale_g,
            "rtol": KD_RTOL}


def phase_kd(tcfg, scfg, seed: int, log: CompileLog):
    """Stage 1 through ``distill.run_chain``; returns the student params."""
    import jax
    import numpy as np
    from repro.core import distill
    from repro.data import BatchLoader, make_dataset_for
    from repro.models import registry
    from repro.types import DistillConfig

    dcfg = DistillConfig(lr=0.01, chain=(tcfg.name, scfg.name))
    big = make_dataset_for(scfg, small=False, seed=seed)
    loader = BatchLoader(big, KD_BATCH, steps=KD_STEPS, seed=seed)
    eval_b = list(big.batches(KD_BATCH, 1, seed=999))
    clip = eval_b[0]["clips"].shape

    # compile the exact KD epoch program run_chain builds (same function,
    # same donation) and look for the lowered Pallas kernel in it
    c0, t0 = log.snapshot(), time.perf_counter()
    engine = distill.make_distill_engine(tcfg, scfg, dcfg)
    key = jax.random.PRNGKey(seed)
    t_shape = jax.eval_shape(lambda: registry.init_params(key, tcfg))
    s_shape = jax.eval_shape(lambda: registry.init_params(key, scfg))
    o_shape = jax.eval_shape(engine.opt.init, s_shape)
    stacked = {k: jax.ShapeDtypeStruct((KD_STEPS,) + v.shape, v.dtype)
               for k, v in eval_b[0].items()}
    hlo = jax.jit(engine._epoch, donate_argnums=(3,)).lower(
        t_shape, s_shape, o_shape, stacked).compile().as_text()
    kernel_lowered = "tpu_custom_call" in hlo
    _report("kd-compile", clip=list(clip), batch=KD_BATCH,
            tpu_custom_call=kernel_lowered,
            wall_s=time.perf_counter() - t0,
            **CompileLog.delta(log.snapshot(), c0))
    _check(kernel_lowered, "the KD epoch has no tpu_custom_call")

    c0, t0 = log.snapshot(), time.perf_counter()
    params, stages = distill.run_chain(
        [tcfg, scfg], dcfg, loader, eval_b, steps_per_stage=KD_STEPS,
        seed=seed)
    jax.block_until_ready(params)
    st = stages[0]
    _report("kd", teacher=st.teacher, student=st.student,
            steps=len(st.losses), losses=st.losses, accuracy=st.accuracy,
            stage_wall_s=st.wall_time_s,
            wall_s=time.perf_counter() - t0,
            **CompileLog.delta(log.snapshot(), c0))
    _check(len(st.losses) == KD_STEPS, "KD ran fewer steps than asked")
    _check(bool(np.isfinite(st.losses).all()) and _all_finite(params),
           "KD loss or student params not finite")

    c0, t0 = log.snapshot(), time.perf_counter()
    for R in (KD_BATCH, 128):
        par = kd_kernel_parity(R, scfg.num_classes, dcfg.alpha,
                               dcfg.temperature, seed)
        _report("kd-kernel-vs-ref", **par)
        _check(par["loss_max_rel_diff"] <= KD_RTOL
               and par["grad_max_rel_diff"] <= KD_RTOL,
               f"KD kernel differs from kd_loss_ref at R={R}")
    _report("kd-kernel-vs-ref-cost", wall_s=time.perf_counter() - t0,
            **CompileLog.delta(log.snapshot(), c0))
    return params


def phase_finetune(params, cfg, seed: int, log: CompileLog):
    """Stage 2 through ``launch.pipeline.finetune``: sync, then async."""
    import numpy as np
    from repro.data import make_dataset_for
    from repro.launch.pipeline import finetune
    from repro.types import FedConfig

    ds = make_dataset_for(cfg, small=True, seed=seed)
    # a sync round advances one global epoch per client
    fed = FedConfig(num_clients=FT_CLIENTS, local_iters_min=1,
                    local_iters_max=3,
                    global_epochs=SYNC_ROUNDS * FT_CLIENTS, seed=seed)
    for mode, f in (("sync", fed),
                    ("async", dataclasses.replace(
                        fed, global_epochs=ASYNC_RECEIVES))):
        c0, t0 = log.snapshot(), time.perf_counter()
        res = finetune(params, cfg, f, ds, FT_BATCH, mode, "scan", seed)
        losses = [h[2] for h in res.history]
        _report(f"finetune-{mode}", clients=FT_CLIENTS, batch=FT_BATCH,
                events=len(losses), losses=losses,
                virtual_wall_s=res.wall_clock_s,
                wall_s=time.perf_counter() - t0,
                **CompileLog.delta(log.snapshot(), c0))
        want = SYNC_ROUNDS if mode == "sync" else ASYNC_RECEIVES
        _check(len(losses) == want, f"{mode}: {len(losses)} events, "
                                    f"expected {want}")
        _check(bool(np.isfinite(losses).all()) and _all_finite(res.params),
               f"{mode}: loss or params not finite")


def _round_inputs(cfg, hs, batch: int, seed: int):
    from repro.data import make_dataset_for
    from repro.types import FedConfig
    ds = make_dataset_for(cfg, small=True, seed=seed)
    batches = [list(ds.batches(batch, h, seed=1000 * seed + k))
               for k, h in enumerate(hs)]
    sizes = [10 * (k + 1) for k in range(len(hs))]
    fed = FedConfig(num_clients=len(hs), local_iters_min=1,
                    local_iters_max=max(hs), seed=seed)
    return batches, sizes, fed


def phase_parity(params, cfg, seed: int, log: CompileLog):
    """One padded sync round: scan engine vs the per-client loop oracle."""
    import jax
    from repro.core import fed_engine, fedavg

    batches, sizes, fed = _round_inputs(cfg, PARITY_H, FT_BATCH, seed)
    c0, t0 = log.snapshot(), time.perf_counter()
    with jax.default_matmul_precision("highest"):
        g_scan, _ = fedavg.fedavg_round(
            params, [iter(b) for b in batches], cfg, fed,
            engine=fed_engine.make_sync_round(cfg, fed), data_sizes=sizes)
        g_loop, _ = fedavg.fedavg_round_loop(
            params, [iter(b) for b in batches], cfg, fed, data_sizes=sizes)
    diff = _max_abs_diff(g_scan, g_loop)
    _report("parity-scan-vs-loop", H=list(PARITY_H), batch=FT_BATCH,
            max_abs_diff=diff, atol=PARAM_ATOL,
            moved=_max_abs_diff(g_loop, params),
            wall_s=time.perf_counter() - t0,
            **CompileLog.delta(log.snapshot(), c0))
    _check(diff <= PARAM_ATOL, "scan round differs from the loop oracle")


def phase_sharded(cfg, seed: int, log: CompileLog):
    """8 clients: shard and hier engines over 4 devices vs scan on one."""
    import jax
    from repro.core import fed_engine, fedavg
    from repro.launch.mesh import make_fleet_mesh
    from repro.models import registry

    params = registry.init_params(jax.random.PRNGKey(seed), cfg)
    batches, sizes, fed = _round_inputs(cfg, SHARD_H, SHARD_BATCH, seed)
    engines = {
        "scan": fed_engine.make_sync_round(cfg, fed),
        "shard": fed_engine.make_sharded_sync_round(
            cfg, fed, mesh=make_fleet_mesh()),
        "hier": fed_engine.make_hierarchical_sync_round(
            cfg, fed, mesh=make_fleet_mesh(edges=2)),
    }
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, engine in engines.items():
            c0, t0 = log.snapshot(), time.perf_counter()
            out[name], losses = fedavg.fedavg_round(
                params, [iter(b) for b in batches], cfg, fed,
                engine=engine, data_sizes=sizes)
            jax.block_until_ready(out[name])
            mesh = getattr(engine, "mesh", None)
            _report(f"round-{name}", clients=len(SHARD_H), batch=SHARD_BATCH,
                    mesh=dict(mesh.shape) if mesh is not None else None,
                    devices=(mesh.devices.size if mesh is not None else 1),
                    last_losses=[l[-1] for l in losses],
                    wall_s=time.perf_counter() - t0,
                    **CompileLog.delta(log.snapshot(), c0))
            _check(_all_finite(out[name]), f"{name}: params not finite")
    for name in ("shard", "hier"):
        diff = _max_abs_diff(out[name], out["scan"])
        _report(f"parity-{name}-vs-scan", max_abs_diff=diff,
                atol=PARAM_ATOL)
        _check(diff <= PARAM_ATOL, f"{name} round differs from scan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-round phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device {json.dumps(dev)}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU backend; nothing was run", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {dev['count']}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.core.compile_cache import use_persistent_cache
    from repro.kernels import ops

    t_start = time.perf_counter()
    cache_dir = use_persistent_cache()
    log = CompileLog()
    _check(not ops._interpret(), "Pallas would run in interpret mode")
    print(f"compile cache {cache_dir}", flush=True)

    student = get_config("resnet3d-18")
    if args.chips == 4:
        phase_sharded(student, args.seed, log)
    else:
        params = phase_kd(get_config("resnet3d-34"), student, args.seed, log)
        phase_finetune(params, student, args.seed, log)
        phase_parity(params, student, args.seed, log)
    _report("total", wall_s=time.perf_counter() - t_start,
            **log.snapshot())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
