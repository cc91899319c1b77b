"""End-to-end training driver (runs on this host's real devices).

Implements the paper's full pipeline on synthetic data:
  stage 1 — server-side knowledge distillation (teacher -> TA -> student);
  stage 2 — federated fine-tuning of the student across a heterogeneous
            client fleet, asynchronously (Algorithm 1) or synchronously
            (FedAvg baseline) or centrally (no clients).

Usage (CPU-scale smoke):
    PYTHONPATH=src python -m repro.launch.train --arch resnet3d-18 \
        --mode async --epochs 20 --reduced
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --mode central --steps 50 --reduced
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import save_params
from repro.configs import get_config
from repro.core import distill, simulator
from repro.core.compile_cache import use_persistent_cache
from repro.core.algorithms import ALGORITHMS, make_algorithm
from repro.core.fleet import (ASYNC_ENGINES, EngineSpec, Fleet, FleetSpec,
                              JETSON_FLEET_HMDB51)
from repro.data import BatchLoader, iid_partition, make_dataset_for
from repro.models import registry
from repro.types import DistillConfig, FedConfig


def build_fleet(n: int):
    base = list(JETSON_FLEET_HMDB51)
    return tuple(base[i % len(base)] for i in range(n))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet3d-18")
    ap.add_argument("--mode", choices=["async", "sync", "central"],
                    default="async")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--epochs", type=int, default=20,
                    help="global epochs E (async/sync)")
    ap.add_argument("--steps", type=int, default=50,
                    help="steps (central mode)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--population", type=int, default=0,
                    help="total fleet population (streaming FleetSpec, "
                         "core/fleet.py): clients materialize on demand, "
                         "so this can be 10^6. 0 = resident fleet of "
                         "--clients devices (legacy)")
    ap.add_argument("--clients-per-round", type=int, default=0,
                    help="per-round subsample size m: sync draws m clients "
                         "per round, async keeps m in flight. 0 = the "
                         "whole population every round (legacy)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--beta", type=float, default=0.7)
    ap.add_argument("--a", type=float, default=0.5)
    ap.add_argument("--theta", type=float, default=0.01)
    ap.add_argument("--trainable", choices=["all", "last_layer"],
                    default="all")
    ap.add_argument("--engine", choices=[e.value for e in EngineSpec],
                    default="scan",
                    help="client execution: compiled lax.scan/vmap engine "
                         "(heterogeneous H^k batches via the padded "
                         "masked scan), 'shard' to additionally split the "
                         "sync round's client axis over this host's "
                         "devices, 'hier' for the two-level edge-"
                         "aggregator tree over the ('edge','clients') "
                         "mesh (both sync-only), or the legacy "
                         "per-iteration loop")
    ap.add_argument("--algorithm", choices=sorted(ALGORITHMS),
                    default="fedprox",
                    help="federated algorithm (core/algorithms.py): "
                         "'fedprox' is the paper's proximal local SGD "
                         "(default; identical to the pre-algorithm-layer "
                         "behavior), 'scaffold' adds SCAFFOLD control "
                         "variates against client drift, 'lowrank' ships "
                         "capacity-scaled low-rank/masked submodel "
                         "updates for constrained uplinks")
    ap.add_argument("--async-window", type=float, default=0.0,
                    help="staleness-bounded micro-batching window W in "
                         "virtual seconds (async mode only): receives "
                         "finishing within W of each other apply as one "
                         "fused server mix and re-dispatch as one padded "
                         "batched program; 0 = event-by-event")
    ap.add_argument("--distill-first", action="store_true",
                    help="run a tiny teacher->student KD stage first "
                         "(see launch/pipeline.py for the full two-stage "
                         "KD -> federated fine-tune driver)")
    ap.add_argument("--kd-kernel", choices=list(distill.KD_KERNELS),
                    default="pallas",
                    help="KD loss implementation: fused Pallas kernel "
                         "(default) or the eager jnp parity oracle")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} family={cfg.family} mode={args.mode}")

    key = jax.random.PRNGKey(args.seed)
    params = registry.init_params(key, cfg)

    if args.distill_first and cfg.family == "resnet3d":
        teacher_cfg = get_config("resnet3d-34")
        if args.reduced:
            teacher_cfg = teacher_cfg.reduced()
        big = make_dataset_for(cfg, small=False, seed=args.seed)
        loader = BatchLoader(big, args.batch, steps=16, seed=args.seed)
        eval_b = list(big.batches(args.batch, 4, seed=999))
        dcfg = DistillConfig(lr=0.01, chain=(teacher_cfg.name, cfg.name))
        params, stages = distill.run_chain(
            [teacher_cfg, cfg], dcfg, loader, eval_b,
            steps_per_stage=16, seed=args.seed, trained_teacher_steps=16,
            kd_kernel=args.kd_kernel)
        for st in stages:
            print(f"  KD {st.teacher} -> {st.student}: "
                  f"acc={st.accuracy:.3f} ({st.wall_time_s:.1f}s)")

    population = args.population or args.clients
    fed = FedConfig(num_clients=population, global_epochs=args.epochs,
                    mixing_beta=args.beta, staleness_a=args.a,
                    prox_theta=args.theta, lr=args.lr,
                    trainable=args.trainable,
                    clients_per_round=args.clients_per_round,
                    seed=args.seed)
    ds = make_dataset_for(cfg, small=True, seed=args.seed + 1)
    t0 = time.time()

    if args.mode == "central":
        from repro.core.fedasync import make_client_step
        from repro.optim import trainable_mask
        step, opt = make_client_step(cfg, fed)
        mask = trainable_mask(params, fed.trainable)
        opt_state = opt.init(params)
        anchor = params
        for i, batch in enumerate(ds.batches(args.batch, args.steps,
                                             seed=args.seed)):
            params, opt_state, loss = step(params, opt_state, anchor, batch,
                                           mask)
            if i % 10 == 0:
                print(f"  step {i:4d} loss {float(loss):.4f}")
        result = {"mode": "central", "final_loss": float(loss),
                  "wall_s": time.time() - t0}
    else:
        if args.population:
            # streaming fleet: clients (profile, shard, H^k) materialize on
            # demand, so resident state is O(sampled), not O(population)
            fleet = Fleet.from_spec(FleetSpec(
                population=population, profiles=JETSON_FLEET_HMDB51,
                dataset=ds, batch_size=args.batch,
                steps=fed.local_iters_max, seed=args.seed,
                partition="shared"))
        else:
            profiles = build_fleet(args.clients)
            parts = iid_partition(max(len(ds), args.clients * 8),
                                  args.clients, seed=args.seed) \
                if hasattr(ds, "__len__") else [None] * args.clients
            data = [BatchLoader(ds, args.batch, steps=fed.local_iters_max,
                                seed=k, indices=parts[k])
                    for k in range(args.clients)]
            fleet = Fleet.from_lists(profiles, data)
        run = simulator.run_async if args.mode == "async" \
            else simulator.run_sync
        eng = args.engine
        if args.mode == "async" \
                and EngineSpec.from_str(eng) not in ASYNC_ENGINES:
            # the async path has no fleet-wide round to shard; its bursts
            # batch through the padded vmap program instead
            print(f"  engine={eng} is sync-only; async uses engine=scan")
            eng = "scan"
        kwargs = {}
        if args.mode == "async":
            kwargs["window"] = args.async_window
        if args.algorithm != "fedprox":
            # fedprox stays on the (bit-identical) default paths
            kwargs["algorithm"] = make_algorithm(args.algorithm)
        res = run(params, cfg, fed, fleet, engine=eng, **kwargs)
        params = res.params
        print(f"  virtual wall-clock {res.wall_clock_s:.0f}s "
              f"final loss {res.final_loss:.4f}")
        if args.mode == "async":
            print(f"  staleness histogram: {res.staleness_hist}")
            if args.async_window > 0:
                print(f"  receive-group histogram (W={args.async_window}): "
                      f"{res.group_hist}")
        result = {"mode": args.mode, "algorithm": args.algorithm,
                  "final_loss": res.final_loss,
                  "virtual_wall_s": res.wall_clock_s,
                  "real_wall_s": time.time() - t0}

    if args.ckpt:
        save_params(params, args.ckpt, extra=result)
        print(f"  saved {args.ckpt}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    use_persistent_cache()
    raise SystemExit(main())
