#!/usr/bin/env python3
"""One cell of the benchmark with the program's span and counter
recorder (``repro.obs``) on in the measured window.

    python3 bench/tools/program_spans.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/tools/program_spans.py --workload <name> --seed <n> --seconds <s> --rehearse

It runs ``run.py`` in this process and changes two things. The window
enables the recorder as it opens and disables it as it closes, and the
program's spans join the harness spans, so the traced run's
``breakdown.idle_gaps`` names each gap by the innermost program span
open in it (``data.*``, ``fed.*``, ``engine.*``, ``server.*``,
``sim.*``). After the harness's result line it prints one more JSON
line, ``{"program": ...}``: the counters, the updates of the window (fed
updates, KD epochs), the per-layer metrics the readers
``metrics/{stage_share,call_share,sim_self_share,useful_step_ratio,
staged_mb_per_update}.py`` give over the window, and each span name's
count, summed and longest seconds. A rehearsal prints counts only.

The harness does not turn the recorder on itself, so its own runs pay
nothing for it; these runs pay the recorder's cost (PERF.md).
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import run as harness  # noqa: E402

METRICS = {"kd": ["stage_share", "call_share", "staged_mb_per_update"],
           "fed": ["stage_share", "call_share", "sim_self_share",
                   "useful_step_ratio", "staged_mb_per_update"]}


def window_updates(kind: str, rec: dict) -> int:
    """Updates of the window: the program's ``updates`` counter (server
    rounds and receives), or for KD its epoch calls."""
    if kind == "kd":
        return sum(1 for _, _, n, _, _ in rec["spans"]
                   if n == "engine.epoch")
    return rec["counts"].get("updates", 0)


def record_windows():
    """Make every ``Ctx`` window record the program; returns the dict
    that receives the record and the window's context."""
    from repro import obs
    got: dict = {}
    start, end = harness.Ctx.window_start, harness.Ctx.window_end

    def window_start(ctx):
        t = start(ctx)
        obs.enable()
        return t

    def window_end(ctx):
        rec = obs.disable()
        t = end(ctx)
        ctx.spans.extend((s, d, n) for s, d, n, _, _ in rec["spans"])
        got.update(record=rec, ctx=ctx)
        return t

    harness.Ctx.window_start = window_start
    harness.Ctx.window_end = window_end
    return got


def summary(got: dict, kind: str, timed: bool) -> dict:
    rec, ctx = got["record"], got["ctx"]
    updates = window_updates(kind, rec)
    out = {"counts": rec["counts"], "updates": updates}
    if not timed:
        return out
    group = "kd" if kind == "kd" else "fed"
    layer = {"program": rec, "window_s": ctx.t1 - ctx.t0,
             "updates": updates}
    metrics = {}
    for m in METRICS[group]:
        name = f"{m}.{group}"
        v = harness.metric_reader(name).read(name, layer)
        if v is not None:
            metrics[name] = v
    spans: dict = {}
    for _, d, n, _, _ in rec["spans"]:
        c, s, longest = spans.get(n, (0, 0.0, 0.0))
        spans[n] = (c + 1, s + d * 1e-9, max(longest, d * 1e-9))
    out.update(metrics=metrics, window_s=layer["window_s"],
               spans={n: {"count": c, "s": s, "max_s": longest}
                      for n, (c, s, longest) in sorted(spans.items())})
    return out


def main(argv=None) -> int:
    args = harness.parse(argv)
    got = record_windows()
    try:
        rc = harness.run(args)
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    kind = harness.load_cell(args.workload)[3]["kind"]
    print(json.dumps({"program": summary(got, kind, not args.rehearse)}),
          flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
