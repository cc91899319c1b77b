"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the exact TPU program on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.kd_loss import kd_loss_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.swa_attention import swa_attention_pallas


# ---------------------------------------------------------------------------
# kd_loss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,V", [(8, 512), (37, 1000), (64, 4096), (3, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0])
def test_kd_loss_sweep(R, V, dtype, alpha, rng):
    s = jnp.asarray(rng.standard_normal((R, V)), dtype)
    t = jnp.asarray(rng.standard_normal((R, V)), dtype)
    lab = jnp.asarray(rng.integers(0, V, R), jnp.int32)
    got = kd_loss_pallas(s, t, lab, alpha, interpret=True)
    want = ref.kd_loss_ref(s, t, lab, alpha)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * max(1.0, float(
                                   jnp.max(jnp.abs(want)))))


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_follows_backend(monkeypatch, backend, interpret):
    """Interpret mode on CPU only, Mosaic on TPU, and no silent
    interpreter on any other backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


def test_kd_loss_jit_wrapper_means(rng):
    s = jnp.asarray(rng.standard_normal((4, 7, 128)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((4, 7, 128)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, 128, (4, 7)), jnp.int32)
    got = ops.kd_loss(s, t, lab, 0.3)
    want = jnp.mean(ref.kd_loss_ref(s.reshape(28, 128), t.reshape(28, 128),
                                    lab.reshape(28), 0.3))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# T -> 0+ blows the MSE term up by 1/T² (tolerance scales with it),
# T >> 1 squashes it to ~0; alpha 0/1 turn off the CE / KD term entirely
@pytest.mark.parametrize("temperature", [1e-3, 0.5, 1.0, 100.0])
@pytest.mark.parametrize("alpha", [0.0, 1.0])
def test_kd_loss_temperature_alpha_extremes(temperature, alpha, rng):
    R, V = 16, 384
    s = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, R), jnp.int32)
    got = kd_loss_pallas(s, t, lab, alpha, temperature=temperature,
                         interpret=True)
    want = ref.kd_loss_ref(s, t, lab, alpha, temperature=temperature)
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * scale)
    if alpha == 1.0:
        # pure CE: temperature must be a strict no-op
        base = kd_loss_pallas(s, t, lab, 1.0, temperature=1.0,
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_kd_loss_masked_rows_exact_noop(rng):
    """Padded rows are *bitwise* no-ops: garbage (NaN/Inf/huge) logits in
    masked rows must not perturb any valid row, and masked outputs are
    exactly zero — forward and backward."""
    R, V = 8, 256
    s = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, R), jnp.int32)
    clean = kd_loss_pallas(s, t, lab, 0.5, interpret=True)

    garbage = jnp.stack([jnp.full((V,), jnp.nan, jnp.float32),
                         jnp.full((V,), jnp.inf, jnp.float32),
                         jnp.full((V,), 1e30, jnp.float32)])
    s_pad = jnp.concatenate([s, garbage])
    t_pad = jnp.concatenate([t, garbage])
    lab_pad = jnp.concatenate([lab, jnp.zeros((3,), jnp.int32)])
    valid = jnp.concatenate([jnp.ones((R,), jnp.float32),
                             jnp.zeros((3,), jnp.float32)])
    padded = kd_loss_pallas(s_pad, t_pad, lab_pad, 0.5, valid=valid,
                            interpret=True)
    assert np.array_equal(np.asarray(padded[:R]), np.asarray(clean))
    assert np.array_equal(np.asarray(padded[R:]), np.zeros(3, np.float32))

    # backward through the custom-vjp rows entry: masked rows get 0 grads
    from repro.kernels.kd_loss import kd_loss_rows

    def total(sp, tp):
        return jnp.sum(kd_loss_rows(sp, tp, lab_pad, 0.5, valid=valid))

    ds, dt_ = jax.grad(total, argnums=(0, 1))(s_pad, t_pad)
    assert np.array_equal(np.asarray(ds[R:]), np.zeros((3, V), np.float32))
    assert np.array_equal(np.asarray(dt_[R:]), np.zeros((3, V), np.float32))
    assert np.isfinite(np.asarray(ds[:R])).all()
    assert np.isfinite(np.asarray(dt_[:R])).all()


@pytest.mark.parametrize("alpha,temperature", [(0.0, 1.0), (1.0, 1.0),
                                               (0.3, 2.0), (0.5, 0.5)])
def test_kd_loss_rows_grad_matches_eager(alpha, temperature, rng):
    """The kernel's analytic custom-vjp backward == jax autodiff through
    the eager oracle (the property the distill engine's training relies
    on when kd_kernel='pallas')."""
    from repro.kernels.kd_loss import kd_loss_rows
    R, V = 12, 320
    s = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    lab = jnp.asarray(rng.integers(0, V, R), jnp.int32)
    w = jnp.asarray(rng.standard_normal(R), jnp.float32)   # mixed cotangent

    def f_kernel(sp, tp):
        return jnp.sum(w * kd_loss_rows(sp, tp, lab, alpha,
                                        temperature=temperature))

    def f_eager(sp, tp):
        return jnp.sum(w * ref.kd_loss_ref(sp, tp, lab, alpha,
                                           temperature=temperature))

    gk = jax.grad(f_kernel, argnums=(0, 1))(s, t)
    ge = jax.grad(f_eager, argnums=(0, 1))(s, t)
    scale = max(1.0, float(jnp.max(jnp.abs(ge[0]))))
    for a, b in zip(gk, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,D,w", [(256, 64, 32), (256, 64, 100),
                                   (128, 128, 128), (512, 64, 200),
                                   (256, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_sweep(S, D, w, dtype, rng):
    BH = 3
    q = jnp.asarray(rng.standard_normal((BH, S, D)) * 0.3, dtype)
    k = jnp.asarray(rng.standard_normal((BH, S, D)) * 0.3, dtype)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), dtype)
    got = swa_attention_pallas(q, k, v, w, q_block=min(128, S),
                               k_block=min(128, S), interpret=True)
    want = ref.swa_attention_ref(q, k, v, w)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_swa_full_attention_equals_window_S(rng):
    BH, S, D = 2, 256, 64
    q = jnp.asarray(rng.standard_normal((BH, S, D)) * 0.2, jnp.float32)
    k = jnp.asarray(rng.standard_normal((BH, S, D)) * 0.2, jnp.float32)
    v = jnp.asarray(rng.standard_normal((BH, S, D)), jnp.float32)
    got = ops.swa_attention(q, k, v, window=0)       # 0 -> full causal
    want = ref.swa_attention_ref(q, k, v, S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_swa_matches_model_attention(rng):
    """Kernel agrees with the model's jnp attention path (GQA folded)."""
    from repro.models.attention import gqa_attention
    B, S, H, D, w = 2, 128, 4, 64, 48
    q = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    want = gqa_attention(q, k, v, window=w, q_chunk=64)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    got = ops.swa_attention(qf, kf, vf, window=w)
    got = got.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,P,N,chunk", [(128, 2, 32, 16, 32),
                                           (256, 3, 64, 16, 64),
                                           (256, 2, 32, 128, 128),
                                           (64, 1, 64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(S, H, P, N, chunk, dtype, rng):
    B = 2
    x = jnp.asarray(rng.standard_normal((B, S, H, P)), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, S, H)),
                                     jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.standard_normal(H) * 0.3, jnp.float32))
    Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, dtype)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, dtype)
    yk, hk = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk, interpret=True)
    yr, hr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    scale = max(1.0, float(jnp.max(jnp.abs(yr))))
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol * scale)
    np.testing.assert_allclose(np.asarray(hk, np.float32),
                               np.asarray(hr, np.float32),
                               rtol=tol, atol=tol * scale)


def test_ssd_chunked_matches_sequential(rng):
    """The chunked algorithm (model + kernel oracle) vs the O(S) recurrence."""
    B, S, H, P, N = 2, 128, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, S, H)),
                                     jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.standard_normal(H) * 0.3, jnp.float32))
    Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    yc, hc = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=32)
    ys, hs = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hs),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# fused decode kernels: ring attend / ladder-extent attend / SSD step
# (parity oracle = the PR-5 einsum decode path in models/attention, ssm)
# ---------------------------------------------------------------------------

from repro.kernels.ssd_scan import ssd_decode_step_pallas
from repro.kernels.swa_attention import (extent_decode_attend_pallas,
                                         ring_decode_attend_pallas)


def _ring_oracle(q, k, v, pos, window):
    """The einsum ring decode attend (gqa_attention + slot positions)."""
    from repro.models.attention import gqa_attention
    B, KV, G, D = q.shape
    W = k.shape[1]
    k_pos = pos - jnp.mod(pos - jnp.arange(W), W)
    out = gqa_attention(q.reshape(B, 1, KV * G, D), k, v, window=window,
                        causal=True, q_offset=pos, k_positions=k_pos,
                        q_chunk=1)
    return out.reshape(B, KV, G, D)


def _extent_oracle(q, k, v, pos, window, k_ext):
    """The einsum k_extent decode attend (slice + k_len mask)."""
    from repro.models.attention import gqa_attention
    B, KV, G, D = q.shape
    out = gqa_attention(q.reshape(B, 1, KV * G, D),
                        k[:, :k_ext], v[:, :k_ext], window=window,
                        causal=True, q_offset=pos, k_len=pos + 1, q_chunk=1)
    return out.reshape(B, KV, G, D)


# odd windows, window 0 (full), W = 1, pos < W (short prompt) and pos >> W
@pytest.mark.parametrize("W,pos,window", [
    (16, 5, 7),        # pos < W: unwritten slots must be masked
    (16, 40, 7),       # wrapped ring, odd window
    (16, 40, 13),      # odd window > half the ring
    (16, 3, 0),        # full attention over a partially written ring
    (1, 0, 1),         # W = 1 edge: only the current token
    (1, 25, 1),
    (17, 33, 17),      # odd ring capacity
])
def test_ring_decode_attend_parity(W, pos, window, rng):
    B, KV, G, D = 3, 2, 3, 16
    q = jnp.asarray(rng.standard_normal((B, KV, G, D)) * 0.4, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, W, KV, D)) * 0.4, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, W, KV, D)), jnp.float32)
    got = ring_decode_attend_pallas(q, k, v, jnp.int32(pos),
                                    jnp.int32(window), interpret=True)
    want = _ring_oracle(q, k, v, jnp.int32(pos), jnp.int32(window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# k_ext at every rung of the pow-2 ladder (min_bucket 4 .. S_max 64)
@pytest.mark.parametrize("k_ext", [4, 8, 16, 32, 64])
@pytest.mark.parametrize("window", [0, 5])
def test_extent_decode_attend_ladder_parity(k_ext, window, rng):
    B, KV, G, D, S_max = 2, 2, 2, 16, 64
    pos = k_ext - 1                    # deepest position the rung serves
    q = jnp.asarray(rng.standard_normal((B, KV, G, D)) * 0.4, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S_max, KV, D)) * 0.4,
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S_max, KV, D)), jnp.float32)
    got = extent_decode_attend_pallas(q, k, v, jnp.int32(pos),
                                      jnp.int32(window), k_ext,
                                      interpret=True)
    want = _extent_oracle(q, k, v, jnp.int32(pos), jnp.int32(window), k_ext)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # shallow position on the same rung: pad slots are k_len-masked
    got0 = extent_decode_attend_pallas(q, k, v, jnp.int32(0),
                                       jnp.int32(window), k_ext,
                                       interpret=True)
    want0 = _extent_oracle(q, k, v, jnp.int32(0), jnp.int32(window), k_ext)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(want0),
                               rtol=1e-5, atol=1e-5)


def test_extent_decode_attend_rejects_bad_extent(rng):
    q = jnp.zeros((1, 1, 1, 8), jnp.float32)
    k = jnp.zeros((1, 16, 1, 8), jnp.float32)
    with pytest.raises(ValueError):
        extent_decode_attend_pallas(q, k, k, jnp.int32(0), jnp.int32(0), 0)
    with pytest.raises(ValueError):
        extent_decode_attend_pallas(q, k, k, jnp.int32(0), jnp.int32(0), 17)


def test_ssd_decode_step_parity(rng):
    """Fused step == the dA/upd/state/y einsum block, including dt=0
    rows (ladder pad steps) being exact state no-ops."""
    B, H, P, N = 3, 4, 8, 16
    xh = jnp.asarray(rng.standard_normal((B, H, P)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, H)),
                                     jnp.float32))
    dt = dt.at[1].set(0.0)            # pad-row: exact no-op on the state
    A = -jnp.exp(jnp.asarray(rng.standard_normal(H) * 0.3, jnp.float32))
    Bm = jnp.asarray(rng.standard_normal((B, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, N)) * 0.5, jnp.float32)
    st = jnp.asarray(rng.standard_normal((B, H, P, N)), jnp.float32)

    dA = jnp.exp(dt * A[None, :])
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt.astype(xh.dtype), xh, Bm)
    st_want = st * dA[..., None, None].astype(st.dtype) + upd
    y_want = jnp.einsum("bhpn,bn->bhp", st_want, Cm)

    y_got, st_got = ssd_decode_step_pallas(xh, dt, A, Bm, Cm, st,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_got), np.asarray(st_want),
                               rtol=1e-5, atol=1e-5)
    # the dt=0 row's state is untouched bit-for-bit
    assert bool(jnp.all(st_got[1] == st[1]))


def test_ssd_decode_step_multi_step_vs_sequential(rng):
    """Iterating the fused step tracks the O(S) sequential reference."""
    B, S, H, P, N = 2, 24, 2, 8, 8
    x = jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, S, H)),
                                     jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.standard_normal(H) * 0.3, jnp.float32))
    Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
    ys_ref, h_ref = ops.ssd_sequential_ref(x, dt, A, Bm, Cm)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, h = ssd_decode_step_pallas(x[:, t], dt[:, t], A, Bm[:, t],
                                      Cm[:, t], h, interpret=True)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(ys_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# family-level fused-vs-einsum decode parity (all five LM families)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "gemma3-12b",
                                  "llama4-scout-17b-a16e", "mamba2-130m",
                                  "hymba-1.5b"])
def test_decode_step_grouped_kernel_parity(arch, rng):
    """One fused decode step == one einsum decode step — same logits to
    fp32 tolerance and the same greedy token, from the same prefilled
    ring cache, for every LM family."""
    from repro.configs import get_config
    from repro.models import lm, registry
    cfg = get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    B, S_max, P = 2, 32, 9
    cache = registry.init_cache(cfg, B, S_max, jnp.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
    logits, cache = registry.prefill(params, cfg, {"tokens": toks}, cache,
                                     q_chunk=P)
    ring = lm.to_ring_cache(cfg, cache, P)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = {}
    for kern in ("einsum", "pallas"):
        outs[kern] = registry.decode_step_grouped(
            params, cfg, tok, dict(ring), jnp.int32(P), k_ext=16,
            decode_kernel=kern)
    lg_e, lg_p = outs["einsum"][0], outs["pallas"][0]
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_e),
                               rtol=2e-5, atol=2e-5)
    assert jnp.array_equal(jnp.argmax(lg_p, -1), jnp.argmax(lg_e, -1))
    for key in outs["einsum"][1]:
        np.testing.assert_allclose(
            np.asarray(outs["pallas"][1][key], np.float32),
            np.asarray(outs["einsum"][1][key], np.float32),
            rtol=2e-5, atol=2e-5, err_msg=key)
