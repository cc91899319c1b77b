"""Batch stacks assembled on the device (the data layer).

``data.stack_batches``, ``fed_engine.pad_client_batches`` /
``stack_client_batches`` and the two stacking branches of
``fedavg.fedavg_round`` put each batch on the device as it is and stack
there: what they return must equal the host ``np.stack`` / zero-padded
reference bit for bit, and the stacking programs must compile once per
(n_clients, H_max) round shape, whatever the H^k draw.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fed_engine, fedavg
from repro.data import SyntheticActionDataset, SyntheticLMDataset, synthetic
from repro.data import stack_batches
from repro.models import registry
from repro.types import FedConfig, ModelConfig

TINY = ModelConfig(name="stack-test-tiny", family="dense", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                   vocab_size=64)
FED = FedConfig(num_clients=3, local_iters_min=1, local_iters_max=3,
                lr=0.01)


def _lm_batches(h, seed, batch=2, seq_len=8):
    ds = SyntheticLMDataset(vocab=TINY.vocab_size, seq_len=seq_len, seed=0)
    return list(ds.batches(batch, h, seed=seed))


def _clip_batches(h, seed):
    ds = SyntheticActionDataset(num_classes=5, samples_per_class=4,
                                frames=2, size=8, seed=0)
    return list(ds.batches(3, h, seed=seed))


def _host_padded(blists, H_max):
    """The host reference: (n, H_max, ...) per key, zeros past H^k."""
    ref = next(b for bl in blists for b in bl)
    out = {k: np.zeros((len(blists), H_max) + v.shape, v.dtype)
           for k, v in ref.items()}
    for c, bl in enumerate(blists):
        for i, b in enumerate(bl):
            for k in out:
                out[k][c, i] = b[k]
    return out


def _assert_bit_equal(got, want):
    assert set(got) == set(want)
    for k in want:
        assert isinstance(got[k], jax.Array)
        g = np.asarray(jax.device_get(got[k]))
        assert g.dtype == want[k].dtype and g.shape == want[k].shape
        np.testing.assert_array_equal(g, want[k])


@pytest.mark.parametrize("make", [_clip_batches, _lm_batches],
                         ids=["float_clips", "int_tokens"])
def test_stack_batches_on_device_equals_np_stack(make):
    batches = make(3, seed=4)
    stacked = stack_batches(iter(batches))
    _assert_bit_equal(stacked, {k: np.stack([b[k] for b in batches])
                                for k in batches[0]})
    assert stack_batches(iter([])) is None
    # already on the device: stacked as they are, same result
    again = stack_batches(iter(jax.device_put(batches)))
    _assert_bit_equal(again, jax.device_get(stacked))


class _Capture(fed_engine.SyncRound):
    """The round engine, keeping a copy of the stack it was handed (the
    round donates the stack itself)."""

    def __call__(self, params_global, client_stacks, **kw):
        self.seen = jax.tree_util.tree_map(jnp.copy, client_stacks)
        return super().__call__(params_global, client_stacks, **kw)


def _through_round(blists):
    """Run ``fedavg_round`` and return the stack its engine saw, after
    checking the round against the loop oracle."""
    params = registry.init_params(jax.random.PRNGKey(0), TINY)
    engine = _Capture(TINY, FED)
    g, losses = fedavg.fedavg_round(params, [iter(b) for b in blists],
                                    TINY, FED, engine=engine)
    g_loop, l_loop = fedavg.fedavg_round_loop(
        params, [iter(b) for b in blists], TINY, FED)
    assert [len(l) for l in losses] == [len(b) for b in blists]
    np.testing.assert_allclose(sum(losses, []), sum(l_loop, []),
                               rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_loop)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    return engine.seen


@pytest.mark.parametrize("Hs,build", [
    ((3, 1, 0, 2),
     lambda bl: fed_engine.pad_client_batches(
         [stack_batches(iter(b)) for b in bl], H_max=3)[0]),
    ((2, 2, 2),
     lambda bl: fed_engine.stack_client_batches(
         [stack_batches(iter(b)) for b in bl])),
    ((3, 1, 0, 2), _through_round),
    ((2, 2, 2), _through_round)],
    ids=["pad_client_batches", "stack_client_batches", "padded_round",
         "homogeneous_round"])
def test_device_assembly_equals_host_zero_padded(Hs, build):
    blists = [_lm_batches(h, seed=10 + c) for c, h in enumerate(Hs)]
    _assert_bit_equal(build(blists), _host_padded(blists, max(Hs)))


def _padded_round_stack(blists):
    fedavg.fedavg_round(registry.init_params(jax.random.PRNGKey(0), TINY),
                        [iter(b) for b in blists], TINY, FED,
                        engine=fed_engine.SyncRound(TINY, FED))


def _pad_client_stack(blists):
    fed_engine.pad_client_batches(
        [jax.device_put({k: np.stack([b[k] for b in bl]) for k in bl[0]})
         if bl else None for bl in blists], H_max=FED.local_iters_max)


@pytest.mark.parametrize("run,program", [
    (_padded_round_stack, synthetic.stack_rows),
    (_pad_client_stack, synthetic.stack_trees)],
    ids=["padded_round", "pad_client_batches"])
def test_assembly_compiles_once_per_round_shape(run, program):
    """The cross-client stack is keyed by (n_clients, H_max) and the
    batch shapes only: three H^k draws, one program. The batch length
    (seq 11) is this test's own, so no other test compiled it before."""
    def blists(Hs, seed):
        return [_lm_batches(h, seed=seed + c, seq_len=11)
                for c, h in enumerate(Hs)]

    before = program._cache_size()
    run(blists((3, 1, 2), 20))
    assert program._cache_size() - before == 1
    for Hs, seed in (((1, 0, 3), 30), ((2, 2, 1), 40)):
        run(blists(Hs, seed))
    assert program._cache_size() - before == 1


def test_pad_slots_share_one_device_zero():
    """Each pad slot of a shape takes the same cached device zero: made
    once, not per round."""
    a = synthetic.device_zeros((2, 5), np.dtype(np.int32))
    assert synthetic.device_zeros((2, 5), np.dtype(np.int32)) is a
    assert a.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(a), np.zeros((2, 5)))
