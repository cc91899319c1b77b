"""Driver of the ``kd`` kind: stage-1 distillation through the program's
``core.distill.DistillEngine``, driven as ``distill.run_chain`` drives it:
``data.stack_batches`` stacks ``epoch_steps`` batches, ``engine.epoch``
runs them as one scan program with the stack donated, and the loss vector
is read back. One engine and one state live from set-up to the window.

Set-up: weights from the seed, the clip pool, the first epoch call (it
compiles; the correctness check follows its steps), ``warm_epochs`` more.
Window: whole epochs until ``--seconds`` have passed since its start.
"""
from __future__ import annotations

import time

import jax
import numpy as np


def run(ctx):
    from repro.core import distill
    from repro.data import stack_batches
    from repro.types import DistillConfig
    import correct as cmp
    import flops

    cfg, tr = ctx.cfg, ctx.traffic
    tcfg, scfg = ctx.model_cfg("teacher"), ctx.model_cfg("student")
    d = cfg["distill"]
    dcfg = DistillConfig(alpha=d["alpha"], temperature=d["temperature"],
                         lr=d["lr"], momentum=d["momentum"],
                         weight_decay=d["weight_decay"],
                         chain=(tcfg.name, scfg.name))
    engine = distill.make_distill_engine(
        tcfg, scfg, dcfg, kd_kernel=d["kd_kernel"],
        use_teacher_targets=d["use_teacher_targets"],
        clip_norm=d["clip_norm"])
    B, E = tr["batch"], tr["epoch_steps"]
    w = ctx.weights(["teacher", "student"])
    teacher, student0 = w["teacher"], w["student"]
    del w
    pool = ctx.pool()
    it = pool.stream()

    def epoch(student, opt):
        stacked = stack_batches(it, limit=E)
        student, opt, ls = engine.epoch(teacher, student, opt, stacked,
                                        donate=True)
        return student, opt, np.asarray(jax.device_get(ls))

    # the first call: the steps the correctness check follows
    student, opt, ls = epoch(student0, engine.opt.init(student0))
    prog = {"loss": ls.tolist(),
            "update": np.asarray(cmp.leaf_norms(opt["mom"])),
            "change": np.asarray(cmp.diff_norms(student, student0))}
    check_rows = [rows for _, _, rows in pool.log]
    del student0
    for _ in range(tr["warm_epochs"]):
        student, opt, ls = epoch(student, opt)

    steps = failed = 0
    t0 = ctx.window_start()
    while True:
        with ctx.span("bench.engine"):
            stacked = stack_batches(it, limit=E)
            student, opt, ls = engine.epoch(teacher, student, opt, stacked,
                                            donate=True)
        with ctx.span("bench.hook"):
            ls = np.asarray(jax.device_get(ls))
        steps += E
        failed += int(np.sum(~np.isfinite(ls)))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    t1 = ctx.window_end()
    del student, opt, teacher

    window_s = t1 - t0
    clips = steps * B

    def check():
        from reference import kd as ref
        w = ctx.weights(["teacher", "student"])
        batches = [pool.batch_of(check_rows[i]) for i in range(E)]
        losses, p, m = ref.follow(w["teacher"], w["student"], batches, d,
                                  row_block=tr["reference_row_block"])
        refr = {"loss": losses, "update": np.asarray(cmp.leaf_norms(m)),
                "change": np.asarray(cmp.diff_norms(p, w["student"]))}
        return cmp.gaps(prog, refr)

    return {
        "e2e": {"kd_clips_per_s": clips / window_s},
        "attempted": steps, "failed": failed,
        "layer": {"clips": clips,
                  "flops": clips * flops.kd_step_flops_per_clip(cfg),
                  "kd_rows": B, "classes": cfg["num_classes"]},
        "counts": {"steps": steps, "clips": clips, "check_steps": E},
        "check": check,
    }
