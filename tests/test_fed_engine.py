"""Scan/vmap client-execution engine vs the legacy loop (parity oracle).

The engine (core/fed_engine.py) must reproduce the per-iteration dispatch
path to float32 tolerance: same local updates, same losses, same simulator
trajectories — including the int8 delta-compression roundtrip, non-uniform
per-client H (the padded masked-scan program), and the shard_map'ed round
on a single-device mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fed_engine, fedasync, fedavg, simulator
from repro.core.simulator import JETSON_FLEET_HMDB51
from repro.data import BatchLoader, SyntheticLMDataset, stack_batches
from repro.models import registry
from repro.types import FedConfig, ModelConfig

TINY = ModelConfig(name="engine-test-tiny", family="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                   vocab_size=64)


def tree_allclose(a, b, rtol=1e-5, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.fixture(scope="module")
def setup():
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    fed = FedConfig(num_clients=4, global_epochs=6, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    return params, fed, ds


def test_scan_client_matches_loop(setup):
    params, fed, ds = setup
    batches = list(ds.batches(2, 3, seed=7))
    w_loop, tau, losses_loop = fedasync.client_update(
        params, 5, iter(batches), TINY, fed, num_iters=3)
    run = fed_engine.make_client_run(TINY, fed)
    w_scan, losses_scan = run(params, stack_batches(iter(batches)))
    assert tau == 5
    np.testing.assert_allclose(np.asarray(losses_scan), losses_loop,
                               rtol=1e-4)
    tree_allclose(w_loop, w_scan)


def test_scan_nonuniform_H_uses_static_cache(setup):
    params, fed, ds = setup
    # a private instance: make_client_run memoizes engines globally, which
    # would leak compile-cache entries from other tests into the count
    run = fed_engine.ClientRun(TINY, fed)
    for H in (1, 3, 3):     # repeat H=3: cache hit, no new entry
        batches = list(ds.batches(2, H, seed=H))
        w_loop, _, losses_loop = fedasync.client_update(
            params, 0, iter(batches), TINY, fed, num_iters=H)
        w_scan, losses_scan = run(params, stack_batches(iter(batches)))
        assert losses_scan.shape == (H,)
        np.testing.assert_allclose(np.asarray(losses_scan), losses_loop,
                                   rtol=1e-4)
        tree_allclose(w_loop, w_scan)
    # one compiled program per distinct (H, trainable)
    assert run.num_compiled == 2


def test_vmap_round_matches_loop(setup):
    params, fed, ds = setup
    batches = [list(ds.batches(2, fed.local_iters_max, seed=k))
               for k in range(3)]
    sizes = [10, 30, 60]
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(b) for b in batches], TINY, fed, data_sizes=sizes)
    g_vmap, l_vmap = fedavg.fedavg_round(
        params, [iter(b) for b in batches], TINY, fed, data_sizes=sizes)
    tree_allclose(g_loop, g_vmap)
    np.testing.assert_allclose(l_vmap, l_loop, rtol=1e-4)


def test_vmap_round_ragged_client_pads(setup):
    """A client that runs out of data no longer breaks the batched round:
    its stack pads to H_max and the iteration mask absorbs the gap."""
    params, fed, ds = setup
    batches = [list(ds.batches(2, fed.local_iters_max, seed=0)),
               list(ds.batches(2, 1, seed=1))]        # ragged H
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(b) for b in batches], TINY, fed)
    g_new, l_new = fedavg.fedavg_round(
        params, [iter(b) for b in batches], TINY, fed)
    assert [len(l) for l in l_new] == [len(l) for l in l_loop]
    tree_allclose(g_loop, g_new)


def test_vmap_round_ragged_within_client_falls_back(setup):
    """Batch shapes that don't stack within one client (e.g. a trailing
    partial batch) drop that client to the per-iteration loop; generators
    must survive (raggedness detected after materialization)."""
    params, fed, ds = setup
    uniform = list(ds.batches(2, fed.local_iters_max, seed=0))
    ragged = list(ds.batches(2, 2, seed=1)) + list(ds.batches(1, 1, seed=2))
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(uniform), iter(ragged)], TINY, fed)
    g_new, l_new = fedavg.fedavg_round(
        params, (b for b in [iter(uniform), iter(ragged)]), TINY, fed)
    assert [len(l) for l in l_new] == [len(l) for l in l_loop]
    np.testing.assert_allclose(np.concatenate([np.asarray(l)
                                               for l in l_new]),
                               np.concatenate([np.asarray(l)
                                               for l in l_loop]), rtol=1e-4)
    tree_allclose(g_loop, g_new)


def test_stack_error_mentions_padded_path(setup):
    """The mixed-shape error must point at pad_client_batches (the padded
    masked-scan round), not at falling back to the per-client loop."""
    params, fed, ds = setup
    stacks = [stack_batches(iter(list(ds.batches(2, h, seed=h))))
              for h in (3, 1)]
    with pytest.raises(ValueError, match="pad_client_batches"):
        fed_engine.stack_client_batches(stacks)
    # and padding refuses mismatched keys even when leaf shapes line up
    renamed = {f"x_{k}": v for k, v in stacks[1].items()}
    with pytest.raises(ValueError, match="structure"):
        fed_engine.pad_client_batches([stacks[0], renamed])


def test_padded_batch_matches_loop(setup):
    """run_batch: clients with H^k < H_max agree with the per-client loop
    oracle; losses past H^k are NaN; the compile cache holds ONE program
    per round shape across different H vectors."""
    params, fed, ds = setup
    run = fed_engine.ClientRun(TINY, fed)   # private: isolate cache counts
    for Hs in ([3, 1, 2], [1, 2, 1], [2, 3, 3]):
        blists = [list(ds.batches(2, h, seed=10 * h + i))
                  for i, h in enumerate(Hs)]
        w_news, losses = run.run_batch(
            params, [stack_batches(iter(b)) for b in blists])
        losses = np.asarray(losses)
        assert losses.shape == (len(Hs), fed.local_iters_max)
        for j, (h, bl) in enumerate(zip(Hs, blists)):
            w_loop, _, l_loop = fedasync.client_update(
                params, 0, iter(bl), TINY, fed, num_iters=h)
            np.testing.assert_allclose(losses[j, :h], l_loop, rtol=1e-4)
            assert np.all(np.isnan(losses[j, h:]))
            tree_allclose(jax.tree_util.tree_map(lambda a, j=j: a[j],
                                                 w_news), w_loop)
    # H^k is traced, not a compile key: 3 different H vectors, 1 program
    assert run.num_compiled == 1


def test_caller_iters_win_over_stack_lengths(setup):
    """An explicit iters= with unequal-length stacks truncates to the
    requested H^k — padding must not silently overwrite it."""
    params, fed, ds = setup
    run = fed_engine.make_client_run(TINY, fed)
    blists = [list(ds.batches(2, 3, seed=1)), list(ds.batches(2, 2, seed=2))]
    stacks = [stack_batches(iter(b)) for b in blists]
    w_news, losses = run.run_batch(params, stacks, iters=[2, 1])
    for j, (h, bl) in enumerate(zip([2, 1], blists)):
        w_loop, _, l_loop = fedasync.client_update(
            params, 0, iter(bl), TINY, fed, num_iters=h)
        np.testing.assert_allclose(np.asarray(losses)[j, :h], l_loop,
                                   rtol=1e-4)
        tree_allclose(jax.tree_util.tree_map(lambda a, j=j: a[j], w_news),
                      w_loop)


def test_padded_compression_roundtrip_parity(setup):
    """The int8 delta roundtrip applied to padded-batch outputs matches
    the loop oracle's compressed updates (what the async server sees)."""
    from repro.core.compression import roundtrip
    params, fed, ds = setup
    Hs = [3, 1]
    blists = [list(ds.batches(2, h, seed=h)) for h in Hs]
    run = fed_engine.make_client_run(TINY, fed)
    w_news, _ = run.run_batch(
        params, [stack_batches(iter(b)) for b in blists])
    for j, (h, bl) in enumerate(zip(Hs, blists)):
        w_loop, _, _ = fedasync.client_update(params, 0, iter(bl), TINY,
                                              fed, num_iters=h)
        w_pad = jax.tree_util.tree_map(lambda a, j=j: a[j], w_news)
        rt_pad, _ = roundtrip(w_pad, params, 8)
        rt_loop, _ = roundtrip(w_loop, params, 8)
        tree_allclose(rt_pad, rt_loop, rtol=1e-3, atol=1e-4)


def test_heterogeneous_round_matches_loop(setup):
    """A fleet with per-client H^k (including an out-of-data client) runs
    as ONE padded program with loop-oracle parity — no per-client
    fallback."""
    params, fed, ds = setup
    batches = [list(ds.batches(2, 3, seed=0)), list(ds.batches(2, 1, seed=1)),
               [], list(ds.batches(2, 2, seed=2))]
    sizes = [10, 30, 20, 40]
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(b) for b in batches], TINY, fed, data_sizes=sizes)
    engine = fed_engine.SyncRound(TINY, fed)    # private: count compiles
    g_pad, l_pad = fedavg.fedavg_round(
        params, [iter(b) for b in batches], TINY, fed, engine=engine,
        data_sizes=sizes)
    assert [len(l) for l in l_pad] == [3, 1, 0, 2]
    assert engine.num_compiled == 1             # one batched program
    np.testing.assert_allclose(
        np.concatenate([np.asarray(l) for l in l_pad]),
        np.concatenate([np.asarray(l) for l in l_loop]), rtol=1e-4)
    tree_allclose(g_loop, g_pad)


def test_sharded_round_single_device_smoke(setup):
    """shard_map round on this host's (1-device) fleet mesh: same layout
    and psum-reduced weighted average as production, loop-oracle parity
    for a heterogeneous H^k fleet."""
    from repro.launch.mesh import make_fleet_mesh
    params, fed, ds = setup
    mesh = make_fleet_mesh()
    assert mesh.axis_names == ("clients",)
    batches = [list(ds.batches(2, h, seed=h)) for h in (3, 1, 2)]
    sizes = [10, 30, 60]
    engine = fed_engine.make_sharded_sync_round(TINY, fed, mesh=mesh)
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(b) for b in batches], TINY, fed, data_sizes=sizes)
    g_sh, l_sh = fedavg.fedavg_round(
        params, [iter(b) for b in batches], TINY, fed, engine=engine,
        data_sizes=sizes)
    assert [len(l) for l in l_sh] == [len(l) for l in l_loop]
    tree_allclose(g_loop, g_sh)
    # memoized: same (cfg, fed, mesh) -> same engine instance
    assert fed_engine.make_sharded_sync_round(TINY, fed, mesh=mesh) \
        is engine


@pytest.mark.parametrize("arch", ["mamba2-130m", "grok-1-314b",
                                  "hymba-1.5b"])
def test_sharded_round_model_families(arch):
    """The shard_map round type-checks every LM family's loss: the SSD
    state, MoE aux and chunked-CE accumulators vary over the client axis
    like the batches they come from, and the round equals the vmap one."""
    from repro.configs import get_config
    from repro.launch.mesh import make_fleet_mesh
    cfg = get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    fed = FedConfig(num_clients=2, local_iters_min=1, local_iters_max=2,
                    lr=0.01)
    ds = SyntheticLMDataset(vocab=cfg.vocab_size, seq_len=8, seed=0)
    stacks = [stack_batches(ds.batches(2, 2, seed=k)) for k in range(2)]
    iters = np.array([2, 1], np.int32)
    g_sh, l_sh = fed_engine.make_sharded_sync_round(
        cfg, fed, mesh=make_fleet_mesh())(params, stacks, iters=iters)
    g_pad, l_pad = fed_engine.make_sync_round(cfg, fed)(params, stacks,
                                                        iters=iters)
    np.testing.assert_allclose(np.asarray(l_sh), np.asarray(l_pad),
                               rtol=1e-5, atol=1e-5)
    tree_allclose(g_sh, g_pad)


def test_run_sync_shard_engine_parity(setup):
    params, fed, ds = setup
    ra = simulator.run_sync(params, TINY, fed, JETSON_FLEET_HMDB51,
                            _fleet_data(ds, fed), engine="shard")
    rb = simulator.run_sync(params, TINY, fed, JETSON_FLEET_HMDB51,
                            _fleet_data(ds, fed), engine="loop")
    assert ra.wall_clock_s == rb.wall_clock_s
    np.testing.assert_allclose([h[2] for h in ra.history],
                               [h[2] for h in rb.history],
                               rtol=1e-3, atol=1e-4)
    tree_allclose(ra.params, rb.params, rtol=1e-3, atol=1e-4)


def _fleet_data(ds, fed):
    return [BatchLoader(ds, 2, steps=4, seed=k)
            for k in range(fed.num_clients)]


@pytest.mark.parametrize("compress_bits", [0, 8])
def test_run_async_engine_parity(setup, compress_bits):
    params, fed, ds = setup
    import dataclasses
    fed = dataclasses.replace(fed, compress_bits=compress_bits)
    ra = simulator.run_async(params, TINY, fed, JETSON_FLEET_HMDB51,
                             _fleet_data(ds, fed), engine="scan")
    rb = simulator.run_async(params, TINY, fed, JETSON_FLEET_HMDB51,
                             _fleet_data(ds, fed), engine="loop")
    # identical event order / virtual clock, float32-level numerics
    assert ra.wall_clock_s == rb.wall_clock_s
    assert ra.staleness_hist == rb.staleness_hist
    np.testing.assert_allclose([h[2] for h in ra.history],
                               [h[2] for h in rb.history],
                               rtol=1e-3, atol=1e-4)
    tree_allclose(ra.params, rb.params, rtol=1e-3, atol=1e-4)


def test_run_sync_engine_parity(setup):
    params, fed, ds = setup
    ra = simulator.run_sync(params, TINY, fed, JETSON_FLEET_HMDB51,
                            _fleet_data(ds, fed), engine="scan")
    rb = simulator.run_sync(params, TINY, fed, JETSON_FLEET_HMDB51,
                            _fleet_data(ds, fed), engine="loop")
    assert ra.wall_clock_s == rb.wall_clock_s
    np.testing.assert_allclose([h[2] for h in ra.history],
                               [h[2] for h in rb.history],
                               rtol=1e-3, atol=1e-4)
    tree_allclose(ra.params, rb.params, rtol=1e-3, atol=1e-4)


def test_unstack_clients_matches_eager_slices():
    """One jitted dispatch must split a client-stacked pytree exactly like
    per-client eager ``a[j]`` slicing (the async burst's fan-out)."""
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.standard_normal((3, 4, 2)), jnp.float32),
               "b": jnp.asarray(rng.standard_normal((3, 5)), jnp.float32)}
    run = fed_engine.ClientRun(TINY, FedConfig(num_clients=3))
    out = run.unstack(stacked, 3)
    assert len(out) == 3
    for j in range(3):
        for got, ref in zip(jax.tree_util.tree_leaves(out[j]),
                            jax.tree_util.tree_leaves(
                                jax.tree_util.tree_map(
                                    lambda a: a[j], stacked))):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_server_mix_shared_across_configs():
    """server_receive(mix=None) must reuse one jitted mix — the program is
    config-independent (beta_t is an argument), so no per-receive or even
    per-FedConfig recompiles."""
    assert fedasync.make_server_update(FedConfig(mixing_beta=0.7)) is \
        fedasync.make_server_update(FedConfig(mixing_beta=0.5))
