"""Optimizers, checkpointing, data pipeline, sharding rules."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import load_params, save_params
from repro.data import (BatchLoader, SyntheticActionDataset,
                        SyntheticLMDataset, dirichlet_partition,
                        iid_partition)
from repro.optim import adamw, apply_mask, sgd, trainable_mask
from repro.optim.schedules import cosine, inverse_sqrt


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def test_sgd_matches_manual():
    opt = sgd(0.1, momentum=0.9, weight_decay=0.01)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.5])}
    st = opt.init(p)
    p1, st = opt.update(g, st, p)
    eff = 0.5 + 0.01 * np.asarray([1.0, -2.0])       # wd
    mom = eff                                         # m1 = g
    np.testing.assert_allclose(np.asarray(p1["w"]),
                               np.asarray([1.0, -2.0]) - 0.1 * mom,
                               rtol=1e-6)
    p2, st = opt.update(g, st, p1)
    eff2 = 0.5 + 0.01 * np.asarray(p1["w"])
    mom2 = 0.9 * mom + eff2
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(p1["w"]) - 0.1 * mom2, rtol=1e-6)


def test_adamw_converges_quadratic():
    opt = adamw(0.1)
    p = {"w": jnp.zeros(3)}
    st = opt.init(p)
    target = jnp.asarray([1.0, -2.0, 0.5])
    for _ in range(300):
        g = {"w": p["w"] - target}
        p, st = opt.update(g, st, p)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(target),
                               atol=1e-2)


def test_trainable_mask_last_layer():
    params = {"embed": jnp.ones((4, 2)), "layers": {"wq": jnp.ones((2, 2))},
              "final_norm": jnp.ones(2), "lm_head": jnp.ones((2, 4))}
    mask = trainable_mask(params, "last_layer")
    assert mask["lm_head"] == 1.0 and mask["final_norm"] == 1.0
    assert mask["layers"]["wq"] == 0.0 and mask["embed"] == 0.0
    g = apply_mask(params, mask)
    assert float(jnp.sum(g["layers"]["wq"])) == 0.0


def test_schedules():
    cs = cosine(1.0, total_steps=100, warmup=10)
    assert float(cs(0)) == 0.0
    assert float(cs(10)) == pytest.approx(1.0, rel=1e-3)
    assert float(cs(100)) == pytest.approx(0.1, rel=1e-2)
    inv = inverse_sqrt(1.0, warmup=4)
    assert float(inv(16)) == 0.25


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, rng):
    params = {"a": jnp.asarray(rng.standard_normal((3, 4)), jnp.float32),
              "nested": {"b": jnp.arange(5, dtype=jnp.int32)},
              "lst": [jnp.ones(2), jnp.zeros((2, 2))]}
    path = os.path.join(tmp_path, "ck")
    save_params(params, path, extra={"step": 7})
    back = load_params(jax.tree_util.tree_map(jnp.zeros_like, params), path)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_server_state_roundtrip(tmp_path):
    from repro.checkpoint import load_server_state, save_server_state
    from repro.core.fedasync import ServerState
    st = ServerState(params={"w": jnp.ones(3)}, t=11, total_updates=42)
    path = os.path.join(tmp_path, "server")
    save_server_state(st, path)
    st2 = load_server_state({"w": jnp.zeros(3)}, path)
    assert st2.t == 11 and st2.total_updates == 42
    np.testing.assert_array_equal(np.asarray(st2.params["w"]), 1.0)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def test_action_dataset_deterministic():
    ds = SyntheticActionDataset(num_classes=4, samples_per_class=4, seed=7)
    b1 = next(ds.batches(4, 1, seed=1))
    b2 = next(SyntheticActionDataset(num_classes=4, samples_per_class=4,
                                     seed=7).batches(4, 1, seed=1))
    np.testing.assert_array_equal(b1["clips"], b2["clips"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])


def test_action_dataset_classes_distinguishable():
    """Same class twice is closer than two different classes (on average)."""
    ds = SyntheticActionDataset(num_classes=4, samples_per_class=4, seed=0,
                                noise=0.1)
    r = np.random.default_rng(0)
    same = np.mean([np.linalg.norm(ds.render(0, r) - ds.render(0, r))
                    for _ in range(5)])
    diff = np.mean([np.linalg.norm(ds.render(0, r) - ds.render(2, r))
                    for _ in range(5)])
    assert diff > same * 0.9


@pytest.mark.parametrize("reduced,clip", [(False, (8, 112, 112, 3)),
                                           (True, (4, 16, 16, 3))],
                         ids=["published", "reduced"])
def test_action_dataset_renders_at_model_input_shape(reduced, clip):
    """Published widths train on the paper's 8x112x112 clips; the reduced
    preset keeps the small clips that CPU tests can afford."""
    from repro.configs import get_config
    from repro.data import make_dataset_for
    cfg = get_config("resnet3d-18")
    if reduced:
        cfg = cfg.reduced()
    b = next(make_dataset_for(cfg).batches(2, 1))
    assert b["clips"].shape == (2,) + clip
    assert b["clips"].dtype == np.float32


def test_lm_dataset_shapes():
    ds = SyntheticLMDataset(vocab=64, seq_len=16, seed=0)
    b = next(ds.batches(3, 1))
    assert b["tokens"].shape == (3, 16) and b["labels"].shape == (3, 16)
    assert b["tokens"].max() < 64
    # labels are next-token of tokens
    full = np.concatenate([b["tokens"][:, :1], b["labels"]], axis=1)
    np.testing.assert_array_equal(b["tokens"][:, 1:], full[:, 1:-1])


def test_partitions():
    parts = iid_partition(100, 4, seed=0)
    allidx = np.sort(np.concatenate(parts))
    np.testing.assert_array_equal(allidx, np.arange(100))
    labels = np.repeat(np.arange(10), 20)
    dparts = dirichlet_partition(labels, 4, alpha=0.1, seed=0)
    assert sum(len(p) for p in dparts) == 200
    # non-IID: at least one client has a skewed class histogram
    h = [np.bincount(labels[p], minlength=10) / max(len(p), 1)
         for p in dparts]
    assert max(hh.max() for hh in h) > 0.2


def test_batch_loader_restartable():
    ds = SyntheticLMDataset(vocab=32, seq_len=8, seed=0)
    loader = BatchLoader(ds, 2, steps=3, seed=5)
    n1 = sum(1 for _ in loader())
    n2 = sum(1 for _ in loader())
    assert n1 == n2 == 3


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def _fake_mesh():
    """AbstractMesh-like stand-in for rule tests (no 256 devices needed)."""
    from jax.sharding import AbstractMesh
    try:
        return AbstractMesh((16, 16), ("data", "model"))
    except TypeError:
        # jax <= 0.4.x signature: a tuple of (axis_name, size) pairs
        return AbstractMesh((("data", 16), ("model", 16)))


def test_param_specs_divisible():
    from repro.configs import get_config
    from repro.models import registry
    from repro.sharding import param_pspecs
    mesh = _fake_mesh()
    for arch in ("grok-1-314b", "hymba-1.5b", "mamba2-130m",
                 "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(
            lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
        specs = param_pspecs(mesh, cfg, shapes)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_l = jax.tree_util.tree_leaves(shapes)
        for spec, leaf in zip(flat_s, flat_l):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                size = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    size *= dict(data=16, model=16)[a]
                assert dim % size == 0, (spec, leaf.shape)


def test_moe_expert_sharding_rule():
    from repro.configs import get_config
    from repro.models import registry
    from repro.sharding import param_pspecs
    mesh = _fake_mesh()
    l4 = get_config("llama4-scout-17b-a16e")     # 16 experts -> expert dim
    shapes = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), l4))
    specs = param_pspecs(mesh, l4, shapes)
    assert tuple(specs["layers"]["moe"]["wi"])[1] == "model"
    gk = get_config("grok-1-314b")               # 8 experts -> tensor 2D
    shapes = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), gk))
    specs = param_pspecs(mesh, gk, shapes)
    si = tuple(specs["layers"]["moe"]["wi"])
    assert si[1] is None and "model" in si


def test_batch_specs_divisibility_guard():
    from repro.configs import get_config
    from repro.sharding import batch_pspecs
    from repro.types import ShapeConfig
    mesh = _fake_mesh()
    cfg = get_config("internlm2-20b")
    batch = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    spec = batch_pspecs(mesh, cfg, batch)
    assert tuple(spec["tokens"])[0] == "data"
    odd = {"tokens": jax.ShapeDtypeStruct((3, 128), jnp.int32)}
    spec = batch_pspecs(mesh, cfg, odd)
    assert tuple(spec["tokens"])[0] is None
