"""The program's spans and counters (``repro.obs``) as the benchmark
reads them: the readers of ``metrics/``, gaps of a trace named by
program spans, and a recorded rehearsal (``tools/program_spans.py``)."""
import json

import pytest

import run
import trace_reduce as tr

MS = 1_000_000


def _layer():
    """A 100-ms window of one sync round: the round (sim.round) stages
    (fed.pad) 0-20 ms, calls the engine 20-30 ms, reads back 30-80 ms,
    and a stack (data.stack) overlapping the pad 10-25 ms."""
    spans = [(0, 90 * MS, "sim.round", -1, 1),
             (0, 20 * MS, "fed.pad", 0, 1),
             (20 * MS, 10 * MS, "engine.pad", 0, 1),
             (30 * MS, 50 * MS, "fed.readback", 0, 1),
             (10 * MS, 15 * MS, "data.stack", -1, 1)]
    counts = {"staged_bytes": 115_600_000, "clip_steps_executed": 96,
              "clip_steps_useful": 64, "updates": 1}
    return {"program": {"spans": spans, "counts": counts},
            "window_s": 0.1, "updates": 1}


@pytest.mark.parametrize("name,value", [
    ("stage_share.fed", 25.0),          # the union 0-25 ms
    ("call_share.fed", 10.0),
    ("sim_self_share.fed", 10.0),       # 90 ms less 80 covered
    ("useful_step_ratio.fed", 100 * 64 / 96),
    ("staged_mb_per_update.fed", 115.6)])
def test_reader_on_a_hand_built_layer(name, value):
    assert run.metric_reader(name).read(name, _layer()) \
        == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "stage_share.kd", "call_share.kd", "sim_self_share.fed",
    "useful_step_ratio.fed", "staged_mb_per_update.kd"])
def test_reader_without_the_program_record_reads_nothing(name):
    layer = {"window_s": 15.0, "updates": 8, "trace": None}
    assert run.metric_reader(name).read(name, layer) is None


def test_program_span_names_the_gap_inside_a_harness_span():
    # device busy 0-10 ms and 50-60 ms; the host, inside bench.program,
    # pads from 12 to 45 ms
    ops = [(0, 10 * MS, "%fusion.1 = a"),
           (50 * MS, 10 * MS, "%fusion.2 = b")]
    t = tr.Trace(devices=[tr.DeviceTrace(0, ops)], spans=[],
                 start_epoch_ns=1_000 * MS)
    assert t.idle_gaps() == [["outside harness spans",
                              pytest.approx(0.04)]]
    t.add_host_spans([(1_000 * MS + 5 * MS, 50 * MS, "bench.program")])
    assert t.idle_gaps() == [["bench.program", pytest.approx(0.04)]]
    t.add_host_spans([(1_000 * MS + 12 * MS, 33 * MS, "fed.pad")])
    assert t.idle_gaps() == [["fed.pad", pytest.approx(0.04)]]


def test_rehearsal_counts_two_thirds_of_the_sync_steps(capsys,
                                                       monkeypatch):
    from repro.core import fed_engine
    import jax
    from tools import program_spans
    monkeypatch.setattr(fed_engine, "_ENGINE_CACHE", {})
    monkeypatch.setattr(run.Ctx, "window_start", run.Ctx.window_start)
    monkeypatch.setattr(run.Ctx, "window_end", run.Ctx.window_end)
    jax.clear_caches()
    rc = program_spans.main(["--workload", "ft-sync.r18.jetson4.b8",
                             "--seed", "4294967311", "--seconds", "2",
                             "--rehearse"])
    assert rc == 0
    *_, result, program = capsys.readouterr().out.strip().splitlines()
    assert json.loads(result)["correct"]
    got = json.loads(program)["program"]
    c = got["counts"]
    assert 3 * c["clip_steps_useful"] == 2 * c["clip_steps_executed"] > 0
    assert c["updates"] == got["updates"] > 0
    assert "metrics" not in got and "spans" not in got
