"""Two-stage pipeline driver: KD compression -> federated fine-tuning.

This is the paper's end-to-end story in one command (§III): stage 1
distils a server-side teacher into the deployable student over the
*full* (synthetic) dataset; stage 2 fine-tunes the distilled student
across the heterogeneous Jetson fleet on each client's *reduced* local
shard, asynchronously (Algorithm 1) or synchronously (FedAvg).

The distilled student params are the fine-tune init — the handoff is a
pytree of identical treedef/shapes to a scratch init, so the federated
engine's round program compiles once regardless of which init it gets.
Both stages run on the batched compiled engines (``core/distill.py``,
``core/fed_engine.py``); the whole pipeline is bit-reproducible under a
fixed ``--seed`` (``params_digest`` in the report certifies it).

Usage (CPU-scale smoke):
    PYTHONPATH=src python -m repro.launch.pipeline --smoke
    PYTHONPATH=src python -m repro.launch.pipeline --arch resnet3d-18 \
        --teacher resnet3d-34 --reduced --mode async --compare-scratch
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.core import distill, simulator
from repro.core.compile_cache import use_persistent_cache
from repro.core.fleet import Fleet
from repro.data import BatchLoader, iid_partition, make_dataset_for
from repro.launch.train import build_fleet
from repro.models import registry
from repro.types import DistillConfig, FedConfig, ModelConfig


def params_digest(params) -> str:
    """sha256 over the param pytree's structure + raw leaf bytes: two runs
    of the pipeline agree iff their digests agree (bit-reproducibility)."""
    h = hashlib.sha256()
    leaves, treedef = jax.tree_util.tree_flatten(params)
    h.update(str(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        h.update(str((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def finetune(params, cfg: ModelConfig, fed: FedConfig, ds, batch: int,
             mode: str, engine: str, seed: int):
    """Stage 2: federated fine-tune from ``params`` over an iid partition
    of the clients' reduced local dataset."""
    parts = iid_partition(max(len(ds), fed.num_clients * 8),
                          fed.num_clients, seed=seed) \
        if hasattr(ds, "__len__") else [None] * fed.num_clients
    data = [BatchLoader(ds, batch, steps=fed.local_iters_max,
                        seed=k, indices=parts[k])
            for k in range(fed.num_clients)]
    fleet = Fleet.from_lists(build_fleet(fed.num_clients), data)
    run = simulator.run_async if mode == "async" else simulator.run_sync
    res = run(params, cfg, fed, fleet, engine=engine)
    return res


def run_pipeline(arch: str = "resnet3d-18", teacher: str = "resnet3d-34",
                 reduced: bool = True, mode: str = "sync",
                 clients: int = 4, epochs: int = 4, batch: int = 4,
                 kd_steps: int = 8, teacher_steps: int = 8,
                 kd_lr: float = 0.01, kd_epoch_len: int | None = None,
                 kd_kernel: str = "pallas", engine: str = "scan",
                 codistill: bool = False, compare_scratch: bool = False,
                 eval_steps: int = 4, seed: int = 0):
    """Run KD compression then federated fine-tuning; returns
    ``(report, params)`` where report is a JSON-serializable dict and
    params the fine-tuned student pytree.
    """
    cfg = get_config(arch)
    tcfg = get_config(teacher)
    if reduced:
        cfg, tcfg = cfg.reduced(), tcfg.reduced()
    t0 = time.time()
    report = {"arch": cfg.name, "teacher": tcfg.name, "mode": mode,
              "kd_kernel": kd_kernel, "seed": seed}

    # ---- stage 1: server-side KD over the full dataset ----------------
    big = make_dataset_for(cfg, small=False, seed=seed)
    loader = BatchLoader(big, batch, steps=kd_steps, seed=seed)
    kd_eval = list(big.batches(batch, eval_steps, seed=999)) \
        if hasattr(big, "batches") else list(loader())
    dcfg = DistillConfig(lr=kd_lr, chain=(tcfg.name, cfg.name))
    if codistill:
        fleet, co = distill.run_codistill(
            [tcfg, cfg], dcfg, loader, kd_eval,
            rounds=max(1, kd_steps // 4), steps_per_round=min(4, kd_steps),
            seed=seed, kd_kernel=kd_kernel)
        params = fleet.member_params(1)       # the deployable student
        report["stage1"] = {"codistill": True,
                            "accuracy": co["accuracy"],
                            "rounds": int(co["losses"].shape[0])}
    else:
        params, stages = distill.run_chain(
            [tcfg, cfg], dcfg, loader, kd_eval, steps_per_stage=kd_steps,
            seed=seed, kd_kernel=kd_kernel,
            trained_teacher_steps=teacher_steps, epoch_len=kd_epoch_len)
        report["stage1"] = {"codistill": False, "stages": [
            {"teacher": s.teacher, "student": s.student,
             "accuracy": s.accuracy, "steps": len(s.losses),
             "compiles": s.compiles, "wall_s": s.wall_time_s}
            for s in stages]}
    report["stage1"]["digest"] = params_digest(params)

    # ---- stage 2: federated fine-tune on the clients' reduced data ----
    # Same seed as stage 1: the clients' reduced dataset draws the same
    # class programs as the server's full set, so KD transfer is real.
    fed = FedConfig(num_clients=clients, global_epochs=epochs, seed=seed)
    ds = make_dataset_for(cfg, small=True, seed=seed)
    res = finetune(params, cfg, fed, ds, batch, mode, engine, seed)
    params = res.params
    held_out = list(ds.batches(batch, eval_steps, seed=777)) \
        if hasattr(ds, "batches") else []
    acc = distill.evaluate(params, cfg, held_out) if held_out else 0.0
    report["stage2"] = {"final_loss": res.final_loss,
                        "virtual_wall_s": res.wall_clock_s,
                        "accuracy": acc}
    report["params_digest"] = params_digest(params)

    if compare_scratch:
        # same fine-tune from a random init: the KD baseline of Table II
        scratch0 = registry.init_params(
            jax.random.fold_in(jax.random.PRNGKey(seed), 1), cfg)
        sres = finetune(scratch0, cfg, fed, ds, batch, mode, engine, seed)
        sacc = distill.evaluate(sres.params, cfg, held_out) \
            if held_out else 0.0
        report["scratch"] = {"final_loss": sres.final_loss,
                             "accuracy": sacc}
    report["real_wall_s"] = time.time() - t0
    return report, params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet3d-18")
    ap.add_argument("--teacher", default="resnet3d-34")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=["async", "sync"], default="sync")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--kd-steps", type=int, default=8)
    ap.add_argument("--teacher-steps", type=int, default=8)
    ap.add_argument("--kd-lr", type=float, default=0.01)
    ap.add_argument("--kd-epoch-len", type=int, default=None,
                    help="KD scan-program length (default: whole stage)")
    ap.add_argument("--kd-kernel", choices=list(distill.KD_KERNELS),
                    default="pallas")
    ap.add_argument("--engine", choices=["scan", "loop", "shard"],
                    default="scan")
    ap.add_argument("--codistill", action="store_true",
                    help="stage 1 via codistillation (peer ensemble) "
                         "instead of the teacher->student chain")
    ap.add_argument("--compare-scratch", action="store_true",
                    help="also fine-tune from a random init and report it")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI preset (reduced, 2 clients, 2 epochs)")
    args = ap.parse_args(argv)

    kw = dict(arch=args.arch, teacher=args.teacher, reduced=args.reduced,
              mode=args.mode, clients=args.clients, epochs=args.epochs,
              batch=args.batch, kd_steps=args.kd_steps,
              teacher_steps=args.teacher_steps, kd_lr=args.kd_lr,
              kd_epoch_len=args.kd_epoch_len, kd_kernel=args.kd_kernel,
              engine=args.engine, codistill=args.codistill,
              compare_scratch=args.compare_scratch, seed=args.seed)
    if args.smoke:
        kw.update(reduced=True, clients=2, epochs=2, batch=2,
                  kd_steps=4, teacher_steps=2, eval_steps=2)
    report, _ = run_pipeline(**kw)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    use_persistent_cache()
    raise SystemExit(main())
