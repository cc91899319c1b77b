"""The host-path span and counter recorder (``repro.obs``) and the spans
and counters the simulator, the fed engines and the data stackers record
through it."""
import time

import pytest

import jax

from repro import obs
from repro.core import fed_engine, simulator
from repro.core.fleet import Fleet
from repro.core.simulator import JETSON_FLEET_HMDB51
from repro.data import SyntheticLMDataset, stack_batches
from repro.models import registry
from repro.types import FedConfig, ModelConfig

TINY = ModelConfig(name="obs-test-tiny", family="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                   vocab_size=64)
BATCH = 2


@pytest.fixture
def recorder():
    """The recorder, switched off again whatever the test does."""
    yield obs
    obs.disable()


def _names(rec):
    return [name for _, _, name, _, _ in rec["spans"]]


def _off_never_on(o):
    assert o.span("a") is o.NOOP
    with o.span("a"):
        o.count("n", 3)
    o.enable()


def _off_opened_before_enable(o):
    s = o.span("a")
    o.enable()
    with s:
        o.count("n", 0)


def _off_opened_in_an_earlier_session(o):
    o.enable()
    s = o.span("a").__enter__()
    o.disable()
    o.enable()
    s.__exit__(None, None, None)


def _off_still_open_at_disable(o):
    o.enable()
    o.span("a").__enter__()


@pytest.mark.parametrize("case", [_off_never_on, _off_opened_before_enable,
                                  _off_opened_in_an_earlier_session,
                                  _off_still_open_at_disable])
def test_recorder_off_records_nothing(recorder, case):
    case(recorder)
    rec = recorder.disable()
    assert rec["spans"] == []
    assert rec["counts"].get("n", 0) == 0
    assert not recorder.enabled()
    assert recorder.span("b") is recorder.NOOP


def test_nesting_gives_parents_and_update_ids(recorder):
    recorder.enable()
    with recorder.span("sim.round"):
        with recorder.span("fed.pad"):
            pass
        with recorder.span("engine.pad"):
            pass
        recorder.count(recorder.UPDATES)
    with recorder.span("sim.round"):
        open_parent = recorder.span("sim.dispatch").__enter__()
        with recorder.span("engine.run"):
            pass
        recorder.count(recorder.UPDATES)
    rec = recorder.disable()
    assert _names(rec) == ["sim.round", "fed.pad", "engine.pad",
                           "sim.round", "engine.run"]
    parents = [p for _, _, _, p, _ in rec["spans"]]
    updates = [u for _, _, _, _, u in rec["spans"]]
    # engine.run's parent, still open at disable, is dropped: its next
    # enclosing span was the second round
    assert open_parent is not recorder.NOOP
    assert parents == [-1, 0, 0, -1, 3]
    assert updates == [1, 1, 1, 2, 2]
    assert rec["counts"] == {recorder.UPDATES: 2}
    for s, d, _, p, _ in rec["spans"]:
        assert d >= 0
        if p >= 0:
            ps, pd = rec["spans"][p][:2]
            assert ps <= s and s + d <= ps + pd


def test_span_starts_on_the_wall_clock(recorder):
    recorder.enable()
    before = time.time_ns()
    with recorder.span("a"):
        time.sleep(0.002)
    after = time.time_ns()
    (start, dur, _, _, _), = recorder.disable()["spans"]
    assert before <= start <= before + 1_000_000
    assert 2_000_000 <= dur <= after - start


def _client_stacks():
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    return [stack_batches(ds.batches(BATCH, h, seed=h)) for h in (1, 3, 2)]


@pytest.mark.parametrize("inputs,build,span", [
    (lambda: list(SyntheticLMDataset(vocab=64, seq_len=8).batches(BATCH, 3)),
     lambda b: stack_batches(iter(b)), "data.stack"),
    (lambda: _client_stacks()[1:2] * 2, fed_engine.stack_client_batches,
     "fed.pad"),
    (_client_stacks,
     lambda s: fed_engine.pad_client_batches(s, H_max=3)[0], "fed.pad")],
    ids=["stack_batches", "stack_client_batches", "pad_client_batches"])
def test_staged_bytes_of_a_stack(recorder, inputs, build, span):
    args = inputs()
    recorder.enable()
    stacked = build(args)
    rec = recorder.disable()
    # assembled on the device: no host bytes staged
    assert rec["counts"] == {"staged_bytes": 0, "device_stacked_bytes": sum(
        l.nbytes for l in jax.tree_util.tree_leaves(stacked))}
    assert all(isinstance(l, jax.Array)
               for l in jax.tree_util.tree_leaves(stacked))
    assert _names(rec) == [span]


def _fleet(fed):
    """The four Jetson profiles, each client fed exactly its H^k batches
    per visit (H^k = 1, 2, 2, 3 by speed rank)."""
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    placeholder = Fleet.from_lists(JETSON_FLEET_HMDB51,
                                   [None] * len(JETSON_FLEET_HMDB51))
    iters = [placeholder.iters(k, fed) for k in range(4)]

    def data(k, h):
        return lambda: ds.batches(BATCH, h, seed=k)
    return Fleet.from_lists(JETSON_FLEET_HMDB51,
                            [data(k, h) for k, h in enumerate(iters)]), iters


def _ancestors(rec, i):
    out = []
    p = rec["spans"][i][3]
    while p >= 0:
        out.append(rec["spans"][p][2])
        p = rec["spans"][p][3]
    return out


def test_run_sync_counts_useful_and_executed_steps(recorder):
    fed = FedConfig(num_clients=4, global_epochs=8, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    fleet, iters = _fleet(fed)
    assert sorted(iters) == [1, 2, 2, 3]
    recorder.enable()
    simulator.run_sync(params, TINY, fed, fleet)
    rec = recorder.disable()
    rounds, n, h_max = 2, 4, 3
    c = rec["counts"]
    assert c[recorder.UPDATES] == rounds
    assert c["clip_steps_executed"] == rounds * n * h_max * BATCH
    assert c["clip_steps_useful"] == rounds * sum(iters) * BATCH
    assert (c["clip_steps_useful"] / c["clip_steps_executed"]
            == sum(iters) / (n * h_max))
    # tokens and labels, (n, H_max, batch, seq) int32 each, every round,
    # pad slots included, all assembled on the device
    assert c["device_stacked_bytes"] == rounds * 2 * n * h_max * BATCH * 8 * 4
    assert c["staged_bytes"] == 0
    names = _names(rec)
    assert names.count("sim.round") == rounds
    assert names.count("fed.pad") == names.count("fed.readback") == rounds
    engine = [i for i, nm in enumerate(names) if nm.startswith("engine.")]
    assert engine
    for i in engine:
        assert "sim.round" in _ancestors(rec, i)
    assert [u for _, _, nm, _, u in rec["spans"] if nm == "sim.round"] \
        == [1, 2]


def test_run_async_event_by_event_runs_no_padding(recorder):
    fed = FedConfig(num_clients=4, global_epochs=7, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    fleet, _ = _fleet(fed)

    def after_kickoff(t, _now, _params):
        if t == 1:              # the batched, padded kickoff is behind
            recorder.enable()

    simulator.run_async(params, TINY, fed, fleet, eval_fn=after_kickoff,
                        eval_every=1, window=0.0)
    rec = recorder.disable()
    c = rec["counts"]
    updates = fed.global_epochs - 1
    assert c[recorder.UPDATES] == updates
    assert c["clip_steps_useful"] == c["clip_steps_executed"] > 0
    names = _names(rec)
    assert names.count("sim.receive") == updates
    assert names.count("server.mix") == updates
    receives = [i for i, nm in enumerate(names) if nm == "sim.receive"]
    assert [rec["spans"][i][4] for i in receives] == list(
        range(1, updates + 1))
    for i, nm in enumerate(names):
        if nm == "server.mix":
            assert _ancestors(rec, i) == ["sim.receive"]
        if nm == "fed.readback":
            assert _ancestors(rec, i) == ["sim.dispatch"]
