#!/usr/bin/env python3
"""Chip benchmark of the federated action-recognition system.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --rehearse

Runs one cell of ``BENCHMARK.json`` (at the root of the checkout) in this
process: set-up (weights and a clip pool from ``--seed``, compiles, the
first steps that the correctness check follows, warm-up), a measured
window of ``--seconds``, then the comparison with the plain reference.
Everything a cell needs is found by name:

- ``configs/<config>.json``: the model configuration as it is run;
- ``traffic/<traffic>.json``: the mix; its ``kind`` names the driver,
  ``drivers/<kind>.py``, whose ``run(ctx)`` drives the program;
- ``metrics/<metric>.py`` (or ``metrics/<prefix>.py`` for
  ``<prefix>.<suffix>``): the reader of a per-layer metric;
- ``limits/<workload>.json``: the limits of the correctness comparison.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``compared``: each compared number with its limit, also printed as the
last lines of stderr. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result. ``--rehearse`` runs the
cell at a tiny size on the CPU and prints counts and the comparison, never
a device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# the size a rehearsal runs at: the program's ``.reduced()`` resnet3d
REHEARSAL_MODEL = {"stem_width": 32, "num_classes": 16, "clip": [4, 16, 16, 3]}
# a traced run measures a window of at most this many seconds: the trace
# of a longer one takes minutes to write and read back
TRACE_WINDOW_S = 15.0
# the profiler traces the device only: its host tracer, at level 1, slowed
# a sync round on a TPU v5e from 0.6 s to 1 s and an async receive by a
# fifth, so the harness keeps its own spans on the host clock (``HostSpan``)
HOST_TRACER_LEVEL = 0


class SetupError(RuntimeError):
    """The cell cannot run here (no chip, unknown device, missing files)."""


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise SetupError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts):
    path = os.path.join(BENCH, *parts)
    if not os.path.exists(path):
        raise SetupError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class HostSpan:
    """A harness span on the host's wall clock, the clock of the trace's
    ``profile_start_time``: appends (start ns since the epoch, duration ns,
    name) to ``out`` on exit."""

    def __init__(self, out: list, name: str):
        self.out, self.name = out, name
        self.start = None

    def __enter__(self):
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.out.append((self.start, time.time_ns() - self.start,
                         self.name))
        return False


class Ctx:
    """What a driver gets: the cell's data, sizes, weights and clip pool,
    and the marks of the measured window."""

    def __init__(self, args, cfg: dict, traffic: dict, log):
        self.seed = args.seed
        self.trace = bool(args.trace)
        self.seconds = (min(args.seconds, TRACE_WINDOW_S) if self.trace
                        else args.seconds)
        self.rehearse = args.rehearse
        self.traffic = dict(traffic)
        self.cfg = dict(cfg)
        if self.rehearse:
            self.traffic.update(traffic.get("rehearse", {}))
            self.cfg.update(REHEARSAL_MODEL)
        self.log = log
        self.trace_dir = os.path.join(ROOT, ".bench_out", "trace")
        self.t0 = self.t1 = None
        self.compiles = {}
        self.spans: list = []         # harness spans of a traced window
        self.setup_marks: dict = {}   # seconds of set-up steps, for stderr

    # -- the program's view of the configuration --------------------------
    def model_cfg(self, role: str):
        """The program's ModelConfig for ``cfg["models"][role]``, checked
        against the configuration file."""
        from repro.configs import get_config
        from repro.configs.resnet3d import BLOCKS
        m = self.cfg["models"][role]
        mc = get_config(m["arch"])
        if self.rehearse:
            mc = mc.reduced()
        if (mc.d_model != self.cfg["stem_width"]
                or mc.num_classes != self.cfg["num_classes"]
                or list(BLOCKS[m["arch"]]) != list(m["blocks"])):
            raise SetupError(f"program config {mc.name} differs from "
                             f"the configuration file ({role})")
        return mc

    def weights(self, roles):
        """Float32 weights for each role, made from the seed in one jitted
        call on the device."""
        import jax
        from reference import resnet3d as rn
        from traffic_gen import key_for

        t = time.perf_counter()

        def make(key):
            ks = jax.random.split(key, len(roles))
            return [rn.init_params(k, self.cfg["models"][r]["blocks"],
                                   self.cfg["stem_width"],
                                   self.cfg["num_classes"])
                    for k, r in zip(ks, roles)]

        out = dict(zip(roles, jax.block_until_ready(
            jax.jit(make)(key_for(self.seed, 0)))))
        self.setup_marks.setdefault("weights_s", time.perf_counter() - t)
        return out

    def pool(self):
        from traffic_gen import Pool
        t, c = self.traffic, self.cfg["clip"]
        t0 = time.perf_counter()
        p = Pool(self.seed, t["pool_clips"], t["batch"],
                 self.cfg["num_classes"], c[0], c[1])
        self.setup_marks.setdefault("pool_s", time.perf_counter() - t0)
        p.span = self.span
        return p

    # -- spans and the window --------------------------------------------
    def span(self, name: str):
        if self.trace and self.t0 is not None and self.t1 is None:
            return HostSpan(self.spans, name)
        return contextlib.nullcontext()

    def window_start(self):
        if self.trace:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = HOST_TRACER_LEVEL
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.compiles["start"] = self.log.snapshot()
        self.t0 = time.perf_counter()
        return self.t0

    def window_end(self):
        self.t1 = time.perf_counter()
        self.compiles["end"] = self.log.snapshot()
        if self.trace:
            import jax
            jax.profiler.stop_trace()
        return self.t1


def devices_or_fail(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if rehearse:
        if devs[0].platform != "cpu":
            raise SetupError("--rehearse runs on the CPU only "
                             "(JAX_PLATFORMS=cpu)")
        return devs[:1]
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = read_json("peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", name.split(".")[0] + ".py")
    return load_module(path, "metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, section: str, workload: str):
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU; prints no device metric")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        return run(args)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


def load_cell(workload: str):
    """(BENCHMARK.json, the workload entry, its configuration, its
    traffic) for a cell, found by name."""
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise SetupError("no BENCHMARK.json at the root of the checkout")
    with open(bench_path) as f:
        bench = json.load(f)
    wl = next((w for w in bench["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    return bench, wl, cfg, read_json("traffic", wl["traffic"] + ".json")


def run(args) -> int:
    bench, wl, cfg, traffic = load_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SetupError("the program (src/repro) is not in this checkout")
    driver_path = os.path.join(BENCH, "drivers", traffic["kind"] + ".py")

    if not args.rehearse:
        # the compile cache lives in the checkout; the program takes it
        # from JAX's own variable
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                               ".jax_cache")
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    devs = devices_or_fail(wl["chips"], args.rehearse)
    kind = devs[0].device_kind
    peaks = None if args.rehearse else peaks_for(kind)
    if not args.rehearse:
        from repro.core.compile_cache import use_persistent_cache
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        use_persistent_cache()
    from compile_log import CompileLog
    import correct as cmp

    log = CompileLog()
    ctx = Ctx(args, cfg, traffic, log)
    driver = load_module(driver_path, "driver_" + traffic["kind"])
    out = driver.run(ctx)
    t_driver = time.perf_counter()
    setup_s = ctx.t0 - T_START
    window_s = ctx.t1 - ctx.t0
    # the peak of the bytes in use: arrays and the running programs'
    # temporaries at one moment (``peak_bytes_reserved`` is a peak of its
    # own, taken at another moment, and is not added to it)
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs)

    trace = None
    if ctx.trace:
        import glob
        import trace_reduce
        files = glob.glob(os.path.join(ctx.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        trace = trace_reduce.load(files[0]) if files else None
        if trace is not None:
            trace.add_host_spans(ctx.spans)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    t_trace = time.perf_counter()
    values = out["check"]()
    t_check = time.perf_counter()
    ok, compared = cmp.judge(values, cmp.limits_for(wl["name"]))
    ok = ok and out["failed"] == 0

    win = ctx.compiles
    layer = dict(out["layer"])
    layer.update(window_s=window_s,
                 window_compiles=(win["end"]["compiles"]
                                  - win["start"]["compiles"]),
                 setup_compile_s=win["start"]["compile_s"],
                 chips=len(devs), peaks=peaks, trace=trace,
                 busy_s=trace.busy_s() if trace else None)
    result = {"correct": ok, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.rehearse:
        result["rehearsal"] = {"window_s": window_s,
                               "window_compiles": layer["window_compiles"],
                               **out["counts"]}
    else:
        metrics = {}
        if ctx.trace:
            for m in cell_metrics(bench, "per_layer", wl["name"]):
                v = metric_reader(m["name"]).read(m["name"], layer)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            e2e = dict(out["e2e"], setup_s=setup_s)
            for m in cell_metrics(bench, "end_to_end", wl["name"]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": mem}
        result["metrics"] = metrics
        result["device"] = device
        if ctx.trace:
            device["busy_s"] = layer["busy_s"]
            device["window_s"] = window_s
            device["per_chip_busy_s"] = (trace.per_device_busy_s()
                                         if trace else [])
            result["breakdown"] = {
                "device_ops": trace.top_ops(10) if trace else [],
                "idle_gaps": trace.idle_gaps(10) if trace else []}
    result["compared"] = compared
    print(json.dumps({"setup": {"setup_s": setup_s, **ctx.setup_marks,
                                **win["start"]},
                      "window": {"window_s": window_s, **win["end"],
                                 **out["counts"]},
                      "after": {"trace_read_s": t_trace - t_driver,
                                "reference_s": t_check - t_trace}}),
          file=sys.stderr)
    for k, v in compared.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
