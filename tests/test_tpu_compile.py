"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is
described, not attached, and refuses what the chip would refuse (block
shapes off the (8, 128) tiling, too much VMEM). Interpret mode cannot see
either. The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.kd_loss import kd_loss_pallas, kd_loss_rows

V = 400          # Kinetics-400 classes: the KD logit width of the paper


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _args(R, sharding):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds((R, V), jnp.float32), sds((R, V), jnp.float32),
            sds((R,), jnp.int32), sds((R,), jnp.float32))


def _fwd(s, t, lab, valid):
    return kd_loss_pallas(s, t, lab, 0.5, temperature=2.0, valid=valid,
                          interpret=False)


def _value_and_grad(s, t, lab, valid):
    def loss(s, t):
        return jnp.sum(kd_loss_rows(s, t, lab, 0.5, temperature=2.0,
                                    valid=valid, interpret=False))
    return jax.value_and_grad(loss, argnums=(0, 1))(s, t)


@pytest.mark.parametrize("fn", [_fwd, _value_and_grad],
                         ids=["forward", "value_and_grad"])
@pytest.mark.parametrize("R", [8, 32, 128])
def test_kd_loss_compiles_for_v5e(one_chip, no_persistent_cache, fn, R):
    compiled = jax.jit(fn).lower(*_args(R, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
