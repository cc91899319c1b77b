"""Mamba2 SSD (state-space duality) blocks — chunked scan, pure jnp.

This is also the oracle (`ref`) the Pallas ssd_scan kernel is validated
against. Group count G=1 (B/C shared across heads), as in Mamba2-130m.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.common import fan_in_init, rms_norm
from repro.types import SSMConfig


def dims(d_model: int, ssm: SSMConfig):
    d_inner = ssm.expand * d_model
    n_heads = d_inner // ssm.head_dim
    conv_dim = d_inner + 2 * ssm.d_state     # x, B, C go through the conv
    return d_inner, n_heads, conv_dim


def init_ssm_params(key, d_model: int, ssm: SSMConfig, num_layers: int,
                    dtype=jnp.float32):
    init = fan_in_init()
    di, nh, conv_dim = dims(d_model, ssm)
    ks = jax.random.split(key, 5)
    L = num_layers
    proj_out = 2 * di + 2 * ssm.d_state + nh      # z, x, B, C, dt
    return {
        "in_proj": init(ks[0], (L, d_model, proj_out), dtype),
        "conv_w": init(ks[1], (L, ssm.d_conv, conv_dim), dtype),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "A_log": jnp.zeros((L, nh), dtype),       # A = -exp(A_log) = -1 init
        "D": jnp.ones((L, nh), dtype),
        "dt_bias": jnp.zeros((L, nh), dtype),
        "norm": jnp.zeros((L, di), dtype),
        "out_proj": init(ks[4], (L, di, d_model), dtype),
    }


def _segsum(x):
    """x: (..., Q) -> (..., Q, Q) lower-tri cumulative sums sum_{j<i<=k}."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, h0=None):
    """SSD forward. Returns (y, final_state).

    xh: (B, S, H, P) inputs per head
    dt: (B, S, H)    positive step sizes (already softplus'ed)
    A:  (H,)         negative decay rates
    Bm, Cm: (B, S, N) state in/out projections (G=1, shared over heads)
    h0: optional initial state (B, H, P, N)
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # zero-pad to a chunk multiple: dt=0 rows are exact no-ops
        # (decay exp(0)=1, contribution dt·x⊗B = 0)
        pad = Q - S % Q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S += pad
    nc = S // Q

    x = xh.reshape(Bsz, nc, Q, H, P)
    dt_c = dt.reshape(Bsz, nc, Q, H)
    B_c = Bm.reshape(Bsz, nc, Q, N)
    C_c = Cm.reshape(Bsz, nc, Q, N)

    dA = dt_c * A[None, None, None, :]                  # (b,c,q,h) negative
    cum = jnp.cumsum(dA, axis=2)                        # within-chunk cumsum

    # --- intra-chunk (quadratic within chunk) ---
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))      # (b,c,h,q,k)
    scores = jnp.einsum("bcqn,bckn->bcqk", C_c, B_c)    # (b,c,q,k)
    xdt = x * dt_c[..., None]                           # fold dt into x
    y = jnp.einsum("bchqk,bcqk,bckhp->bcqhp", L, scores, xdt)

    # --- chunk states ---
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)     # (b,c,q,h)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn",
                        dt_c * decay_states, B_c, x)
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))          # (b,c,h)

    # --- inter-chunk recurrence ---
    # zeros_like keeps the chunk states' shard_map typing, so the scan
    # carry matches its data-varying output
    init = h0 if h0 is not None else jnp.zeros_like(states[:, 0])

    def body(h, xs):
        st, dec = xs                                    # (b,h,p,n), (b,h)
        h_out = h                                       # state entering chunk
        h = h * dec[..., None, None] + st
        return h, h_out

    sts = states.transpose(1, 0, 2, 3, 4)               # (c,b,h,p,n)
    decs = chunk_decay.transpose(1, 0, 2)               # (c,b,h)
    h_final, h_prev = jax.lax.scan(body, init.astype(jnp.float32),
                                   (sts.astype(jnp.float32),
                                    decs.astype(jnp.float32)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)            # (b,c,h,p,n)

    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", C_c, jnp.exp(cum),
                         h_prev.astype(x.dtype))
    y = (y + y_inter).reshape(Bsz, S, H, P)
    return y[:, :S_orig], h_final.astype(x.dtype)


def ssm_forward(p, x, ssm: SSMConfig, state=None, conv_state=None,
                d_model: int | None = None, seq_lens=None,
                kernel: str = "einsum"):
    """Full Mamba2 block (minus residual). x: (B, S, d).

    Training/prefill path. Returns (out, (ssm_state, conv_state)).

    ``kernel="pallas"`` runs the SSD core through the chunked Pallas scan
    (``kernels.ops.ssd_scan``); requires ``state is None`` (no carried-in
    initial state — training/scoring, not chunked prefill).

    ``seq_lens`` (B,) int32 marks positions >= seq_lens[b] as right-padding
    (bucketed prefill): their dt is zeroed — an *exact* no-op on the state
    recurrence (decay exp(0)=1, contribution dt·x⊗B=0, the same mechanism
    ``ssd_chunked`` uses for its own chunk padding) — and the returned
    conv_state is gathered from the window ending at each row's last real
    token instead of the (padded) end of the sequence.  Outputs at pad
    positions are garbage; real positions and both states are bit-identical
    to running the unpadded sequence.
    """
    B, S, d = x.shape
    di, nh, conv_dim = dims(d, ssm)
    N = ssm.d_state

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)

    # causal depthwise conv over (x, B, C)
    pad = jnp.zeros((B, ssm.d_conv - 1, conv_dim), xbc.dtype) \
        if conv_state is None else conv_state
    xbc_pad = jnp.concatenate([pad, xbc], axis=1)
    if seq_lens is None:
        new_conv_state = xbc_pad[:, -(ssm.d_conv - 1):, :]
    else:
        # window ending at each row's last real token: xbc_pad index
        # d_conv-1+t holds input t, so inputs P-d_conv+1..P-1 live at
        # indices P..P+d_conv-2
        idx = (jnp.asarray(seq_lens, jnp.int32)[:, None]
               + jnp.arange(ssm.d_conv - 1)[None, :])
        new_conv_state = jnp.take_along_axis(xbc_pad, idx[:, :, None],
                                             axis=1)
    acc = jnp.zeros_like(xbc)
    for i in range(ssm.d_conv):
        acc = acc + xbc_pad[:, i:i + S, :] \
            * p["conv_w"][i][None, None, :].astype(acc.dtype)
    xbc = jax.nn.silu(acc + p["conv_b"][None, None, :].astype(acc.dtype))

    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    xh = xs.reshape(B, S, nh, ssm.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    if seq_lens is not None:
        active = (jnp.arange(S)[None, :]
                  < jnp.asarray(seq_lens, jnp.int32)[:, None])
        dt = dt * active[..., None].astype(dt.dtype)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if kernel == "pallas":
        if state is not None:
            raise ValueError("kernel='pallas' does not take an initial "
                             "state; use the einsum path for chunked prefill")
        Q = min(ssm.chunk, S)
        padn = (Q - S % Q) % Q
        if padn:   # dt=0 pad rows are exact state no-ops (see ssd_chunked)
            xh_p = jnp.pad(xh, ((0, 0), (0, padn), (0, 0), (0, 0)))
            dt_p = jnp.pad(dt, ((0, 0), (0, padn), (0, 0)))
            Bm_p = jnp.pad(Bm, ((0, 0), (0, padn), (0, 0)))
            Cm_p = jnp.pad(Cm, ((0, 0), (0, padn), (0, 0)))
            y, h_final = ops.ssd_scan(xh_p, dt_p, A, Bm_p, Cm_p, ssm.chunk)
            y = y[:, :S]
        else:
            y, h_final = ops.ssd_scan(xh, dt, A, Bm, Cm, ssm.chunk)
    elif kernel == "einsum":
        y, h_final = ssd_chunked(xh, dt, A, Bm, Cm, ssm.chunk, h0=state)
    else:
        raise ValueError(f"unknown ssm kernel {kernel!r}")
    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(y.dtype))
    return out.astype(x.dtype), (h_final, new_conv_state)


def ssm_decode_step(p, x, ssm: SSMConfig, state, conv_state,
                    kernel: str = "einsum"):
    """One-token recurrent step. x: (B, 1, d). state: (B, H, P, N),
    conv_state: (B, d_conv-1, conv_dim). Returns (out, (state, conv_state)).

    ``kernel="pallas"`` fuses the recurrence (decay + rank-1 update +
    readout) into ``kernels.ops.ssd_decode_step`` — one HBM round trip
    for the state, the update tensor never materialized."""
    B, _, d = x.shape
    di, nh, conv_dim = dims(d, ssm)
    N = ssm.d_state

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))[:, 0]
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)

    window = jnp.concatenate([conv_state.astype(xbc.dtype),
                              xbc[:, None, :]], axis=1)
    new_conv_state = window[:, 1:, :]
    conv_out = jnp.einsum("bkc,kc->bc", window,
                          p["conv_w"].astype(xbc.dtype)) \
        + p["conv_b"].astype(xbc.dtype)
    xbc = jax.nn.silu(conv_out)

    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    xh = xs.reshape(B, nh, ssm.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))    # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if kernel == "pallas":
        y, state = ops.ssd_decode_step(xh, dt, A, Bm, Cm, state)
    elif kernel == "einsum":
        dA = jnp.exp(dt * A[None, :])                           # (B, H)
        # h <- dA * h + dt * x ⊗ B
        upd = jnp.einsum("bh,bhp,bn->bhpn", dt.astype(xh.dtype), xh, Bm)
        state = state * dA[..., None, None].astype(state.dtype) + upd
        y = jnp.einsum("bhpn,bn->bhp", state, Cm)
    else:
        raise ValueError(f"unknown decode kernel {kernel!r}")
    y = y + xh * p["D"][None, :, None].astype(y.dtype)
    y = y.reshape(B, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("bk,kd->bd", y, p["out_proj"].astype(y.dtype))[:, None, :]
    return out.astype(x.dtype), (state, new_conv_state)
