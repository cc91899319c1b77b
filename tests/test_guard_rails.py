"""Runtime guard rails: the PR-2 and PR-5 compile/transfer claims as
executable invariants.

``guard_rails()`` makes every *implicit* host->device transfer an error
(and checks for tracer leaks); ``compile_budget(cache, n)`` pins the
``JitCache`` compile delta. Together they assert the steady state of the
two compiled hot paths: the padded fed round re-runs new H^k draws with
zero new programs and zero hidden transfers, and the serving ladder
replays a whole stream without compiling or syncing implicitly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import fed_engine
from repro.core.serving import ContinuousBatcher
from repro.data import SyntheticLMDataset, stack_batches
from repro.models import registry
from repro.types import FedConfig, ModelConfig

pytestmark = pytest.mark.guard_rails

TINY = ModelConfig(name="guard-test-tiny", family="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                   vocab_size=64)


def test_padded_round_one_compile_no_implicit_transfers(guard_rails,
                                                        compile_budget):
    """PR-2 invariant: H^k is traced, not a compile key — after one
    warm-up, new H vectors run with ZERO new programs and zero implicit
    host->device transfers (all inputs are device_put up front)."""
    fed = FedConfig(num_clients=3, global_epochs=2, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    run = fed_engine.ClientRun(TINY, fed)   # private: isolate cache counts
    mask = jax.tree_util.tree_map(
        lambda _: jnp.asarray(1.0, jnp.float32), params)

    def padded(Hs, seed0):
        blists = [list(ds.batches(2, h, seed=seed0 + i))
                  for i, h in enumerate(Hs)]
        stacked, lens = fed_engine.pad_client_batches(
            [stack_batches(iter(b)) for b in blists],
            H_max=fed.local_iters_max)
        return (jax.device_put(jax.tree_util.tree_map(jnp.asarray,
                                                      stacked)),
                jnp.asarray(lens, jnp.int32))

    stacked, iters = padded([3, 1, 2], 10)
    with compile_budget(run, 1, exact=True):   # warm-up traces the program
        run.run_batch(params, stacked, iters=iters, mask=mask)

    for k, Hs in enumerate(([1, 2, 1], [2, 3, 3])):
        stacked, iters = padded(Hs, 40 + 10 * k)
        with guard_rails(), compile_budget(run, 0, exact=True):
            w_news, losses = run.run_batch(params, stacked, iters=iters,
                                           mask=mask)
        la = jax.device_get(losses)
        for j, h in enumerate(Hs):
            assert np.all(np.isfinite(la[j, :h]))
            assert np.all(np.isnan(la[j, h:]))
    assert run.num_compiled == 1


def test_scaffold_padded_round_steady_state(guard_rails, compile_budget):
    """PR-10 invariant: a stateful algorithm (SCAFFOLD) rides the SAME
    padded masked-scan contract — per-client control variates and the
    server variate are traced arguments, so after one warm-up a new H^k
    draw runs with ZERO new programs and zero implicit transfers."""
    from repro.core.algorithms import Scaffold
    fed = FedConfig(num_clients=3, global_epochs=2, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    alg = Scaffold()
    run = fed_engine.ClientRun(TINY, fed, algorithm=alg)  # private cache
    mask = jax.tree_util.tree_map(
        lambda _: jnp.asarray(1.0, jnp.float32), params)
    ctx = jax.device_put(alg.ctx_for(params))
    states = jax.device_put(alg.stacked_states(params, range(3)))

    def padded(Hs, seed0):
        blists = [list(ds.batches(2, h, seed=seed0 + i))
                  for i, h in enumerate(Hs)]
        stacked, lens = fed_engine.pad_client_batches(
            [stack_batches(iter(b)) for b in blists],
            H_max=fed.local_iters_max)
        return (jax.device_put(jax.tree_util.tree_map(jnp.asarray,
                                                      stacked)),
                jnp.asarray(lens, jnp.int32))

    stacked, iters = padded([3, 1, 2], 10)
    with compile_budget(run, 1, exact=True):   # warm-up traces the program
        out = run.run_batch(params, stacked, iters=iters, mask=mask,
                            server_ctx=ctx, states=states)
    assert len(out) == 4                       # (w, states, msgs, losses)

    for k, Hs in enumerate(([1, 2, 1], [2, 3, 3])):
        stacked, iters = padded(Hs, 40 + 10 * k)
        with guard_rails(), compile_budget(run, 0, exact=True):
            _, new_states, _, losses = run.run_batch(
                params, stacked, iters=iters, mask=mask,
                server_ctx=ctx, states=states)
        la = jax.device_get(losses)
        for j, h in enumerate(Hs):
            assert np.all(np.isfinite(la[j, :h]))
            assert np.all(np.isnan(la[j, h:]))
        # the new variates are well-formed (the state output is real work,
        # not a passthrough)
        for leaf in jax.tree_util.tree_leaves(jax.device_get(new_states)):
            assert np.all(np.isfinite(leaf))
    assert run.num_compiled == 1


def test_padded_round_from_host_batches_no_implicit_transfers(
        guard_rails, compile_budget):
    """A sync round fed host batches assembles its padded stack on the
    device: each batch goes over by an explicit ``device_put`` and the
    pad slots share one device zero, so after one warm-up a new H^k draw
    runs with zero new programs (the round's and the stack's) and zero
    implicit host->device transfers."""
    from repro.core import fedavg
    from repro.data import synthetic
    fed = FedConfig(num_clients=3, global_epochs=2, local_iters_min=1,
                    local_iters_max=3, lr=0.01)
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    engine = fed_engine.SyncRound(TINY, fed)   # private: isolate counts
    mask = jax.tree_util.tree_map(
        lambda _: jnp.asarray(1.0, jnp.float32), params)

    def round_(Hs, seed0):
        blists = [list(ds.batches(2, h, seed=seed0 + i))
                  for i, h in enumerate(Hs)]
        return fedavg.fedavg_round(params, [iter(b) for b in blists], TINY,
                                   fed, engine=engine, mask=mask)

    with compile_budget(engine, 1, exact=True):
        round_([3, 1, 2], 10)
    stacks = synthetic.stack_rows._cache_size()
    for k, Hs in enumerate(([1, 2, 1], [2, 0, 3])):
        with guard_rails(), compile_budget(engine, 0, exact=True):
            _, losses = round_(Hs, 40 + 10 * k)
        assert [len(l) for l in losses] == Hs
        assert all(np.isfinite(l).all() for l in losses)
    assert synthetic.stack_rows._cache_size() == stacks


def test_serving_ladder_steady_state_no_compiles(guard_rails,
                                                 compile_budget, rng):
    """PR-5 invariant: decode programs are bounded by the bucket ladder,
    and an identical second stream replays entirely warm — zero new
    programs, zero implicit transfers, bit-identical outputs."""
    cfg = get_config("hymba-1.5b").reduced()
    params = registry.init_params(jax.random.PRNGKey(8), cfg)
    lengths, max_new = (3, 9, 21), (20, 12, 30)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]

    srv = ContinuousBatcher(params, cfg, max_slots=2, max_len=64,
                            min_bucket=4, decode_mode="ring")
    for p, m in zip(prompts, max_new):
        srv.submit(p, max_new=m)
    done = srv.run()
    assert 2 <= srv.decode_compiles <= len(srv.decode_buckets)

    for p, m in zip(prompts, max_new):
        srv.submit(p, max_new=m)
    with guard_rails(), compile_budget(srv._jits, 0, exact=True):
        done2 = srv.run()                  # cumulative completed list
    assert [r.out for r in done2[len(done):]] == [r.out for r in done]


def test_fused_decode_steady_state_no_compiles(guard_rails,
                                               compile_budget, rng):
    """PR-7 invariant: the fused-Pallas decode path obeys the same
    compile discipline as the einsum oracle — warm decode programs are
    bounded by the K-extent ladder, and a second identical stream runs
    with zero new programs and zero implicit host transfers (the kernels
    take traced pos/window operands, never compile keys or host syncs)."""
    cfg = get_config("hymba-1.5b").reduced()
    params = registry.init_params(jax.random.PRNGKey(8), cfg)
    lengths, max_new = (3, 9, 21), (20, 12, 30)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]

    srv = ContinuousBatcher(params, cfg, max_slots=2, max_len=64,
                            min_bucket=4, decode_mode="ring",
                            decode_kernel="pallas")
    for p, m in zip(prompts, max_new):
        srv.submit(p, max_new=m)
    done = srv.run()
    assert 2 <= srv.decode_compiles <= len(srv.decode_buckets)

    for p, m in zip(prompts, max_new):
        srv.submit(p, max_new=m)
    with guard_rails(), compile_budget(srv._jits, 0, exact=True):
        done2 = srv.run()
    assert [r.out for r in done2[len(done):]] == [r.out for r in done]

    # the oracle kernel must produce the very same stream
    srv_e = ContinuousBatcher(params, cfg, max_slots=2, max_len=64,
                              min_bucket=4, decode_mode="ring",
                              decode_kernel="einsum")
    for p, m in zip(prompts, max_new):
        srv_e.submit(p, max_new=m)
    assert [r.out for r in srv_e.run()] == [r.out for r in done]


# ---------------------------------------------------------------------------
# PR-8: distillation as a compiled fleet workload
# ---------------------------------------------------------------------------

STUDENT = ModelConfig(name="guard-test-student", family="dense",
                      num_layers=1, d_model=16, num_heads=2, num_kv_heads=2,
                      d_ff=32, vocab_size=64)


def _device_stack(ds, batch, steps, seed):
    stacked = stack_batches(iter(ds.batches(batch, steps, seed=seed)))
    return jax.device_put(jax.tree_util.tree_map(jnp.asarray, stacked))


def test_distill_epoch_steady_state_no_compiles(guard_rails,
                                                compile_budget):
    """PR-8 invariant: a warm KD epoch (teacher fwd + student step per
    scan iteration, fused Pallas KD loss) is ONE program — fresh epochs at
    the same (H, batch) shape run with zero new compiles and zero
    implicit host->device transfers."""
    from repro.core.distill import DistillEngine
    from repro.data import SyntheticLMDataset
    from repro.types import DistillConfig

    dcfg = DistillConfig(lr=0.01, batch_size=2)
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    engine = DistillEngine(TINY, STUDENT, dcfg)   # private: isolate counts
    t_params = registry.init_params(jax.random.PRNGKey(0), TINY)
    params = registry.init_params(jax.random.PRNGKey(1), STUDENT)
    opt = engine.opt.init(params)

    stacked = _device_stack(ds, 2, 3, seed=1)
    with compile_budget(engine, 1, exact=True):    # warm-up traces it
        params, opt, losses = engine.epoch(t_params, params, opt, stacked)

    for seed in (2, 3):
        stacked = _device_stack(ds, 2, 3, seed=seed)
        with guard_rails(), compile_budget(engine, 0, exact=True):
            params, opt, losses = engine.epoch(t_params, params, opt,
                                               stacked)
        assert np.all(np.isfinite(jax.device_get(losses)))
    assert engine.num_compiled == 1


def test_kd_to_finetune_handoff_no_recompile(guard_rails, compile_budget):
    """PR-8 invariant: the KD -> fine-tune handoff is pure data. The fed
    engine's round program is keyed on shapes only, so feeding it
    distilled student params instead of a scratch init triggers ZERO new
    compiles and zero implicit transfers."""
    from repro.core.distill import DistillEngine
    from repro.data import SyntheticLMDataset
    from repro.types import DistillConfig

    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=8, seed=0)
    fed = FedConfig(num_clients=2, global_epochs=2, local_iters_min=2,
                    local_iters_max=2, lr=0.01)
    rnd = fed_engine.SyncRound(TINY, fed)    # private: isolate cache counts
    scratch = registry.init_params(jax.random.PRNGKey(0), TINY)
    mask = jax.tree_util.tree_map(
        lambda _: jnp.asarray(1.0, jnp.float32), scratch)
    weights = jnp.full((2,), 0.5, jnp.float32)

    def client_stack(seed0):
        stacks = [stack_batches(iter(ds.batches(2, 2, seed=seed0 + k)))
                  for k in range(2)]
        both = {k: np.stack([s[k] for s in stacks]) for k in stacks[0]}
        return jax.device_put(jax.tree_util.tree_map(jnp.asarray, both))

    stacks = client_stack(10)
    with compile_budget(rnd, 1, exact=True):       # warm the round program
        rnd(scratch, stacks, weights, mask=mask)

    # stage 1: distill a student of the SAME deployable arch (self-KD at
    # test scale), then hand its params to the warm round program
    dcfg = DistillConfig(lr=0.01, batch_size=2)
    engine = DistillEngine(TINY, TINY, dcfg)
    opt = engine.opt.init(scratch)
    distilled, _, _ = engine.epoch(
        registry.init_params(jax.random.PRNGKey(3), TINY),
        scratch, opt, _device_stack(ds, 2, 3, seed=5))

    stacks = client_stack(20)
    with guard_rails(), compile_budget(rnd, 0, exact=True):
        new_global, losses = rnd(distilled, stacks, weights, mask=mask)
    assert np.all(np.isfinite(jax.device_get(losses)))
    assert rnd.num_compiled == 1
