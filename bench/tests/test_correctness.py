"""The correctness comparison at the rehearsal size on the CPU: sound runs
of every cell pass; the same runs with a fault planted under the timed
path fail; the control (the reference in bfloat16) fails a limit.

Each case drives a whole run (``run.py --rehearse``) in this process."""
import json
from types import SimpleNamespace

import jax
import pytest

import run

CELLS = ["kd.r34-r18.b64", "ft-sync.r18.jetson4.b8",
         "ft-async.r18.jetson4.b8"]


def _drive(workload, capsys, monkeypatch):
    from repro.core import fed_engine
    monkeypatch.setattr(fed_engine, "_ENGINE_CACHE", {})
    jax.clear_caches()
    rc = run.main(["--workload", workload, "--seed", "4294967311",
                   "--seconds", "2", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _plant(monkeypatch, workload, fault):
    """Break the step the timed path runs: return its state unchanged, or
    leave out half of the batch and take the mean over the rest."""
    from repro.core import algorithms, distill

    def half(batch):
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}

    if workload.startswith("kd"):
        orig = distill.DistillEngine._step

        def step(self, tp, p, o, b):
            if fault == "half_batch":
                return orig(self, tp, p, o, half(b))
            _, _, loss = orig(self, tp, p, o, b)
            return p, o, loss
        monkeypatch.setattr(distill.DistillEngine, "_step", step)
    else:
        orig = algorithms.FedAlgorithm.client_step

        def client_step(self, ctx, carry, batch):
            if fault == "half_batch":
                return orig(self, ctx, carry, half(batch))
            _, loss = orig(self, ctx, carry, batch)
            return carry, loss
        monkeypatch.setattr(algorithms.FedAlgorithm, "client_step",
                            client_step)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, capsys, monkeypatch):
    res = _drive(workload, capsys, monkeypatch)
    assert res["correct"], res["compared"]
    assert res["rehearsal"]["window_compiles"] == 0
    assert "metrics" not in res and "device" not in res


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(workload, fault, capsys, monkeypatch):
    _plant(monkeypatch, workload, fault)
    res = _drive(workload, capsys, monkeypatch)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    import correct as cmp
    from tools import controls
    _, _, cfg, traffic = run.load_cell(workload)
    ctx = run.Ctx(SimpleNamespace(seed=21, seconds=0, trace=0,
                                  rehearse=True), cfg, traffic, None)
    gaps = cmp.gaps(controls.readings(ctx, "control"),
                    controls.readings(ctx, "reference"))
    ok, compared = cmp.judge(gaps, cmp.limits_for(workload))
    assert not ok, compared
