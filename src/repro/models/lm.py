"""Decoder-only LM covering dense / moe / ssm / hybrid / vlm / audio-prefix.

Layer params are stacked on a leading L axis and consumed by lax.scan; the
per-layer attention window (0 = full) rides along as a scanned scalar so
heterogeneous patterns (gemma3 5:1 local:global, hymba global layers) share
one code path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import chunked_lm_loss, normal_init, rms_norm
from repro.types import ModelConfig


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def windows(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.asarray([cfg.window_for_layer(i) for i in range(cfg.num_layers)],
                       jnp.int32)


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    L, d = cfg.num_layers, cfg.d_model
    layers: dict = {
        "ln1": jnp.zeros((L, d), dtype),
    }
    if cfg.family != "ssm":
        layers["ln2"] = jnp.zeros((L, d), dtype)
    if cfg.family in ("dense", "moe", "hybrid", "vlm"):
        layers["attn"] = attn_mod.init_attn_params(ks[0], cfg, L, dtype)
    if cfg.family in ("dense", "vlm", "hybrid"):
        layers["mlp"] = mlp_mod.init_mlp_params(ks[1], d, cfg.d_ff, L, dtype)
    if cfg.family == "moe":
        layers["moe"] = moe_mod.init_moe_params(ks[2], d, cfg.d_ff, cfg.moe,
                                                L, dtype)
    if cfg.family in ("ssm", "hybrid"):
        layers["ssm"] = ssm_mod.init_ssm_params(ks[3], d, cfg.ssm, L, dtype)
    if cfg.family == "hybrid":
        layers["branch_norm_attn"] = jnp.zeros((L, d), dtype)
        layers["branch_norm_ssm"] = jnp.zeros((L, d), dtype)

    params = {
        "embed": normal_init(0.02)(ks[4], (cfg.vocab_size, d), dtype),
        "layers": layers,
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(0.02)(ks[5], (d, cfg.vocab_size),
                                              dtype)
    return params


def lm_head_weight(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Layer body — one code path for train / prefill / decode
# ---------------------------------------------------------------------------

def _layer(cfg: ModelConfig, lp, x, window, positions, mode: str,
           cache=None, pos=0, q_chunk: int = 1024, moe_ctx=None,
           cache_slice_window: int = 0, k_extent: int = 0, seq_lens=None,
           decode_kernel: str = "einsum"):
    """One layer. mode: 'train' | 'prefill' | 'decode'.

    Returns (x, aux_loss, new_cache).  ``seq_lens`` (B,) marks right-padded
    bucketed-prefill rows: attention needs no mask (pad keys sit at
    positions the causal mask already hides from real queries) but the SSM
    recurrence does — see ``ssm_forward``.

    The attention cache may be uniform (``{"k", "v"}`` of capacity S_max)
    or a ring buffer (``{"k_win", "v_win"}`` of capacity W, decode only —
    see ``init_ring_cache``); ``new_cache`` mirrors whichever layout came
    in. ``k_extent`` (static) bounds the K-extent a uniform-cache decode
    attends against (see ``attn_forward``).

    ``decode_kernel``: "einsum" (jnp oracle) or "pallas" (fused decode
    kernels — ring attend, extent attend, SSD step); decode mode only.
    """
    aux = jnp.float32(0.0)
    new_cache: dict = {}

    def run_ssm(h):
        if mode == "decode":
            return ssm_mod.ssm_decode_step(lp["ssm"], h, cfg.ssm,
                                           cache["ssm_state"],
                                           cache["conv_state"],
                                           kernel=decode_kernel)
        return ssm_mod.ssm_forward(lp["ssm"], h, cfg.ssm,
                                   seq_lens=seq_lens)

    def run_attn(h):
        if mode == "train":
            return attn_mod.attn_forward(lp["attn"], h, cfg=cfg,
                                         window=window, positions=positions,
                                         q_chunk=q_chunk)
        if "k_win" in cache:     # ring-buffer SWA decode
            a, (rk, rv) = attn_mod.ring_decode_attend(
                lp["attn"], h, cfg=cfg, ring_k=cache["k_win"],
                ring_v=cache["v_win"], pos=pos, window=window,
                kernel=decode_kernel)
            return a, {"k_win": rk, "v_win": rv}
        attn_cache = {"k": cache["k"], "v": cache["v"]}
        idx = 0 if mode == "prefill" else pos
        kern = decode_kernel if mode == "decode" else "einsum"
        return attn_mod.attn_forward(lp["attn"], h, cfg=cfg, window=window,
                                     positions=positions, cache=attn_cache,
                                     cache_index=idx, q_chunk=q_chunk,
                                     cache_slice_window=cache_slice_window,
                                     k_extent=k_extent, kernel=kern)

    if cfg.family == "ssm":
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        out, (st, cs) = run_ssm(h)
        if mode != "train":
            new_cache = {"ssm_state": st, "conv_state": cs}
        return x + out, aux, new_cache

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a, ac = run_attn(h)
        s, (st, cs) = run_ssm(h)
        mixed = 0.5 * (rms_norm(a, lp["branch_norm_attn"], cfg.norm_eps)
                       + rms_norm(s, lp["branch_norm_ssm"], cfg.norm_eps))
        x = x + mixed.astype(x.dtype)
        if mode != "train":
            new_cache = {**ac, "ssm_state": st, "conv_state": cs}
    else:
        a, ac = run_attn(h)
        x = x + a
        if mode != "train":
            new_cache = dict(ac)

    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_forward(lp["moe"], h2, cfg.moe, cfg.act,
                                     moe_ctx=moe_ctx,
                                     dropless=mode != "train")
    else:
        y = mlp_mod.mlp_forward(lp["mlp"], h2, cfg.act)
    return x + y, aux, new_cache


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, tokens: jax.Array,
                 prefix_embeds: Optional[jax.Array] = None,
                 dtype=None) -> jax.Array:
    x = params["embed"][tokens]
    if dtype is not None:
        x = x.astype(dtype)
    if cfg.prefix_len and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def forward_hidden(params, cfg: ModelConfig, tokens: jax.Array,
                   prefix_embeds: Optional[jax.Array] = None,
                   remat: bool = True, q_chunk: int = 1024,
                   dtype=None, act_pspec=None, moe_ctx=None):
    """Returns (hidden (B, S, d), aux_loss). ``act_pspec`` optionally
    constrains the residual stream between layers (sequence parallelism —
    shrinks stored remat residuals; see launch/steps.py)."""
    x = embed_inputs(params, cfg, tokens, prefix_embeds, dtype)
    S = x.shape[1]
    positions = jnp.arange(S)
    win = windows(cfg)

    # per-layer aux losses come out as scan outputs, not a carry: under a
    # shard_map a constant initial carry would not match the data-varying
    # sums (see models.common.chunked_lm_loss)
    def body(x, xs):
        lp, w = xs
        x, a, _ = _layer(cfg, lp, x, w, positions, "train", q_chunk=q_chunk,
                         moe_ctx=moe_ctx)
        if act_pspec is not None:
            x = jax.lax.with_sharding_constraint(x, act_pspec)
        return x, a

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, aux = jax.lax.scan(body, x, (params["layers"], win))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(aux)


def loss_fn(params, cfg: ModelConfig, batch: dict, remat: bool = True,
            q_chunk: int = 1024, loss_chunk: int = 512, dtype=None,
            act_pspec=None, moe_ctx=None):
    """Next-token CE (+ MoE aux). batch: tokens (B,S), labels (B,S)[, prefix].

    With a prefix (vlm/audio), labels cover only the token part.
    """
    hidden, aux = forward_hidden(params, cfg, batch["tokens"],
                                 batch.get("prefix_embeds"), remat=remat,
                                 q_chunk=q_chunk, dtype=dtype,
                                 act_pspec=act_pspec, moe_ctx=moe_ctx)
    if cfg.prefix_len and batch.get("prefix_embeds") is not None:
        hidden = hidden[:, cfg.prefix_len:, :]
    head = lm_head_weight(params, cfg).astype(hidden.dtype)
    ce = chunked_lm_loss(hidden, head, batch["labels"], chunk=loss_chunk)
    return ce + aux, {"ce": ce, "aux": aux}


def logits_fn(params, cfg: ModelConfig, tokens, prefix_embeds=None,
              remat: bool = False, dtype=None):
    hidden, _ = forward_hidden(params, cfg, tokens, prefix_embeds,
                               remat=remat, dtype=dtype)
    return jnp.einsum("bsd,dv->bsv", hidden,
                      lm_head_weight(params, cfg).astype(hidden.dtype))


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------

def swa_layer_ids(cfg: ModelConfig):
    return [i for i in range(cfg.num_layers) if cfg.window_for_layer(i) > 0]


def global_layer_ids(cfg: ModelConfig):
    return [i for i in range(cfg.num_layers) if cfg.window_for_layer(i) == 0]


def init_ring_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16):
    """Decode cache with per-layer-kind sizing: full-attention layers get
    ``max_len`` buffers; SWA layers get ring buffers of their window —
    for gemma3 (5 local : 1 global, w=1024, S=32k) this is 5.1× less cache
    memory and HBM traffic than the uniform cache (beyond-paper §Perf).
    Rings are capped at ``max_len`` — positions never exceed it, so a
    window wider than the cache would only buy dead slots."""
    L = cfg.num_layers
    c: dict = {}
    if cfg.family in ("dense", "moe", "hybrid", "vlm"):
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        gl, wl = global_layer_ids(cfg), swa_layer_ids(cfg)
        if gl:
            c["k"] = jnp.zeros((len(gl), batch, max_len, kv, hd), dtype)
            c["v"] = jnp.zeros((len(gl), batch, max_len, kv, hd), dtype)
        if wl:
            W = min(cfg.sliding_window, max_len)
            c["k_win"] = jnp.zeros((len(wl), batch, W, kv, hd), dtype)
            c["v_win"] = jnp.zeros((len(wl), batch, W, kv, hd), dtype)
    if cfg.family in ("ssm", "hybrid"):
        di, nh, conv_dim = ssm_mod.dims(cfg.d_model, cfg.ssm)
        c["ssm_state"] = jnp.zeros((L, batch, nh, cfg.ssm.head_dim,
                                    cfg.ssm.d_state), dtype)
        c["conv_state"] = jnp.zeros((L, batch, cfg.ssm.d_conv - 1, conv_dim),
                                    dtype)
    return c


def ring_source_positions(last, W: int) -> jnp.ndarray:
    """Absolute position each W-ring slot holds once position ``last``
    has been written: slot ``s`` holds the latest ``p <= last`` with
    ``p ≡ s (mod W)``; negative = never written (decode masks those).
    ``last`` may be a scalar or a ``(B,)`` batch (a trailing slot axis is
    appended) — the ONE definition of the ring layout, shared by cache
    conversion, serving install, and (transposed) the decode-side mask in
    ``attention.ring_decode_attend``."""
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - jnp.mod(last - jnp.arange(W), W)


def to_ring_cache(cfg: ModelConfig, cache: dict, pos) -> dict:
    """Convert a full (uniform) cache filled up to ``pos`` exclusive into
    the ring layout (slot s of a W-ring holds the latest p ≡ s mod W)."""
    out = {}
    gl, wl = global_layer_ids(cfg), swa_layer_ids(cfg)
    if "k" in cache:
        if gl:
            idx = jnp.asarray(gl)
            out["k"] = cache["k"][idx]
            out["v"] = cache["v"][idx]
        if wl:
            W = min(cfg.sliding_window, cache["k"].shape[2])
            p_of_slot = ring_source_positions(pos - 1, W).reshape(W)
            take = jnp.clip(p_of_slot, 0, cache["k"].shape[2] - 1)
            widx = jnp.asarray(wl)
            out["k_win"] = jnp.take(cache["k"][widx], take, axis=2)
            out["v_win"] = jnp.take(cache["v"][widx], take, axis=2)
    for key in ("ssm_state", "conv_state"):
        if key in cache:
            out[key] = cache[key]
    return out


def decode_step_ring(params, cfg: ModelConfig, token, cache, pos,
                     dtype=None):
    """One decode step against a ring cache (python-unrolled layers so
    each layer's window is static). Matches decode_step numerically."""
    x = params["embed"][token][:, None, :]
    if dtype is not None:
        x = x.astype(dtype)
    positions = pos + jnp.zeros((1,), jnp.int32)
    gl, wl = global_layer_ids(cfg), swa_layer_ids(cfg)
    gmap = {layer: j for j, layer in enumerate(gl)}
    wmap = {layer: j for j, layer in enumerate(wl)}
    new_cache = dict(cache)
    for i in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        w = cfg.window_for_layer(i)
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)

        def run_attn_i(h):
            if w > 0:
                a, (rk, rv) = attn_mod.ring_decode_attend(
                    lp["attn"], h, cfg=cfg, ring_k=cache["k_win"][wmap[i]],
                    ring_v=cache["v_win"][wmap[i]], pos=pos, window=w)
                return a, {"k_win": rk, "v_win": rv}
            a, ac = attn_mod.attn_forward(
                lp["attn"], h, cfg=cfg, window=jnp.int32(0),
                positions=positions,
                cache={"k": cache["k"][gmap[i]], "v": cache["v"][gmap[i]]},
                cache_index=pos, q_chunk=1)
            return a, {"k": ac["k"], "v": ac["v"]}

        if cfg.family == "ssm":
            out, (st, cs) = ssm_mod.ssm_decode_step(
                lp["ssm"], h, cfg.ssm, cache["ssm_state"][i],
                cache["conv_state"][i])
            x = x + out
            upd = {"ssm_state": st, "conv_state": cs}
        elif cfg.family == "hybrid":
            a, upd = run_attn_i(h)
            so, (st, cs) = ssm_mod.ssm_decode_step(
                lp["ssm"], h, cfg.ssm, cache["ssm_state"][i],
                cache["conv_state"][i])
            mixed = 0.5 * (rms_norm(a, lp["branch_norm_attn"], cfg.norm_eps)
                           + rms_norm(so, lp["branch_norm_ssm"],
                                      cfg.norm_eps))
            x = x + mixed.astype(x.dtype)
            upd = dict(upd)
            upd["ssm_state"] = st
            upd["conv_state"] = cs
        else:
            a, upd = run_attn_i(h)
            x = x + a
        if cfg.family != "ssm":
            h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = moe_mod.moe_forward(lp["moe"], h2, cfg.moe, cfg.act,
                                           dropless=True)
            else:
                y = mlp_mod.mlp_forward(lp["mlp"], h2, cfg.act)
            x = x + y
        for key, val in upd.items():
            j = wmap[i] if key.endswith("_win") else \
                (gmap[i] if key in ("k", "v") else i)
            new_cache[key] = new_cache[key].at[j].set(
                val.astype(new_cache[key].dtype))
    cache = new_cache
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0, :],
                        lm_head_weight(params, cfg).astype(x.dtype))
    return logits, cache


def _kind_runs(cfg: ModelConfig):
    """Contiguous same-kind layer runs, in layer order:
    ``[("swa" | "full", [layer ids]), ...]``.

    ``decode_step_ring`` python-unrolls all L layers, which makes the
    decode program (and its compile) O(L).  Grouping by kind instead lets
    each run scan its layers as ONE program body — every SWA layer shares
    the static window W and every full layer the uniform cache, so within
    a run the layer stack is scan-homogeneous.  gemma3's 5:1 local:global
    pattern yields ~L/5 runs of two alternating kinds.
    """
    runs: list = []
    for i in range(cfg.num_layers):
        kind = "swa" if cfg.window_for_layer(i) > 0 else "full"
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(i)
        else:
            runs.append((kind, [i]))
    return runs


def decode_step_grouped(params, cfg: ModelConfig, token, cache, pos,
                        k_ext: int = 0, dtype=None,
                        decode_kernel: str = "einsum"):
    """One decode step against an ``init_ring_cache`` layout, scanning
    contiguous same-kind layer runs (``_kind_runs``).

    SWA layers attend against their W-slot ring buffers
    (``ring_decode_attend`` — O(W) HBM per step); full-attention layers
    update their uniform cache in place and attend against its first
    ``k_ext`` positions (0 = all of them), masked at ``pos + 1`` — with
    ``k_ext >= pos + 1`` that is bit-identical to the unsliced attend,
    and O(k_ext) HBM per step.  Unlike ``decode_step_ring`` this is
    vmap/scan-friendly: the program is O(#runs), not O(L), so a serving
    batcher can vmap it over a slot batch without an L-times-unrolled
    trace.  Greedy tokens match ``decode_step`` (SWA softmax sums run in
    ring order, so floats may differ in the last ulp).

    ``decode_kernel="pallas"`` fuses every decode attend/recurrence into
    the Pallas decode kernels (see ``kernels/ops.py``) — same math, one
    HBM pass per cache.
    """
    if cfg.family == "ssm":      # no attention: ring layout == uniform
        return decode_step(params, cfg, token, cache, pos, dtype=dtype,
                           decode_kernel=decode_kernel)
    x = params["embed"][token][:, None, :]
    if dtype is not None:
        x = x.astype(dtype)
    positions = pos + jnp.zeros((1,), jnp.int32)
    wmap = {layer: j for j, layer in enumerate(swa_layer_ids(cfg))}
    gmap = {layer: j for j, layer in enumerate(global_layer_ids(cfg))}
    has_ssm = cfg.family == "hybrid"
    outs: dict = {key: [] for key in cache}
    for kind, ids in _kind_runs(cfg):
        i0, i1 = ids[0], ids[-1] + 1
        lp = jax.tree_util.tree_map(lambda a: a[i0:i1], params["layers"])
        if kind == "swa":
            j0, j1 = wmap[ids[0]], wmap[ids[-1]] + 1
            cl = {"k_win": cache["k_win"][j0:j1],
                  "v_win": cache["v_win"][j0:j1]}
            win = jnp.full((len(ids),), cfg.sliding_window, jnp.int32)
        else:
            j0, j1 = gmap[ids[0]], gmap[ids[-1]] + 1
            cl = {"k": cache["k"][j0:j1], "v": cache["v"][j0:j1]}
            win = jnp.zeros((len(ids),), jnp.int32)
        if has_ssm:
            cl["ssm_state"] = cache["ssm_state"][i0:i1]
            cl["conv_state"] = cache["conv_state"][i0:i1]

        def body(x, xs, _kind=kind):
            lp_i, w_i, cl_i = xs
            x, _, nc = _layer(cfg, lp_i, x, w_i, positions, "decode",
                              cache=cl_i, pos=pos, q_chunk=1,
                              k_extent=k_ext if _kind == "full" else 0,
                              decode_kernel=decode_kernel)
            return x, nc

        x, ncs = jax.lax.scan(body, x, (lp, win, cl))
        for key, val in ncs.items():
            outs[key].append(val.astype(cache[key].dtype))
    cache = {key: (vals[0] if len(vals) == 1 else jnp.concatenate(vals, 0))
             for key, vals in outs.items()}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0, :],
                        lm_head_weight(params, cfg).astype(x.dtype))
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    L = cfg.num_layers
    c: dict = {}
    if cfg.family in ("dense", "moe", "hybrid", "vlm"):
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        c["k"] = jnp.zeros((L, batch, max_len, kv, hd), dtype)
        c["v"] = jnp.zeros((L, batch, max_len, kv, hd), dtype)
    if cfg.family in ("ssm", "hybrid"):
        di, nh, conv_dim = ssm_mod.dims(cfg.d_model, cfg.ssm)
        c["ssm_state"] = jnp.zeros((L, batch, nh, cfg.ssm.head_dim,
                                    cfg.ssm.d_state), dtype)
        c["conv_state"] = jnp.zeros((L, batch, cfg.ssm.d_conv - 1, conv_dim),
                                    dtype)
    return c


def _scan_cached(params, cfg, x, positions, cache, mode, pos, q_chunk,
                 seq_lens=None, decode_kernel: str = "einsum"):
    win = windows(cfg)

    def body(carry, xs):
        x, aux = carry
        lp, w, cl = xs
        x, a, nc = _layer(cfg, lp, x, w, positions, mode, cache=cl, pos=pos,
                          q_chunk=q_chunk, seq_lens=seq_lens,
                          decode_kernel=decode_kernel)
        return (x, aux + a), nc

    (x, _), new_cache = jax.lax.scan(
        body, (x, jnp.float32(0.0)), (params["layers"], win, cache))
    return x, new_cache


def prefill(params, cfg: ModelConfig, tokens, cache,
            prefix_embeds=None, q_chunk: int = 1024, dtype=None,
            lengths=None):
    """Fill the cache from position 0; returns (last_logits (B, V), cache).

    ``lengths`` (B,) int32 enables *bucketed* prefill: each row's tokens
    beyond lengths[b] are right-padding to a shared compile-friendly
    sequence length. Logits are gathered at each row's last real position,
    the SSM/conv states stop exactly there (see ``ssm_forward``), and the
    pad keys written into the KV cache are causally invisible to every
    real query and overwritten by decode before they could be attended —
    outputs are bit-identical to an unpadded per-row prefill.
    """
    x = embed_inputs(params, cfg, tokens, prefix_embeds, dtype)
    S = x.shape[1]
    seq_lens = None
    if lengths is not None:
        seq_lens = jnp.asarray(lengths, jnp.int32)
        if cfg.prefix_len and prefix_embeds is not None:
            seq_lens = seq_lens + cfg.prefix_len
    x, cache = _scan_cached(params, cfg, x, jnp.arange(S), cache,
                            "prefill", pos=0, q_chunk=q_chunk,
                            seq_lens=seq_lens)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if seq_lens is None:
        last = x[:, -1, :]
    else:
        last = jnp.take_along_axis(
            x, (seq_lens - 1)[:, None, None], axis=1)[:, 0, :]
    logits = jnp.einsum("bd,dv->bv", last,
                        lm_head_weight(params, cfg).astype(x.dtype))
    return logits, cache


def decode_step(params, cfg: ModelConfig, token, cache, pos, dtype=None,
                unroll: bool = False, window_slice: bool = False,
                decode_kernel: str = "einsum"):
    """One autoregressive step. token: (B,) int32; pos: scalar position.

    Returns (logits (B, V), new_cache).

    ``unroll=True`` python-unrolls the layer loop so each layer's window is
    STATIC, enabling ``window_slice``: SWA layers attend against a
    dynamic-slice of the last `window` cache positions — O(window) HBM
    traffic per step instead of O(S_max) (§Perf, beyond-paper).
    """
    x = params["embed"][token][:, None, :]
    if dtype is not None:
        x = x.astype(dtype)
    positions = pos + jnp.zeros((1,), jnp.int32)
    if not unroll:
        x, cache = _scan_cached(params, cfg, x, positions, cache,
                                "decode", pos=pos, q_chunk=1,
                                decode_kernel=decode_kernel)
    else:
        new_cache = dict(cache)
        for i in range(cfg.num_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            cl = {k: v[i] for k, v in cache.items()}
            w = cfg.window_for_layer(i)
            csw = w if (window_slice and w > 0) else 0
            x, _, nc = _layer(cfg, lp, x, jnp.int32(w), positions, "decode",
                              cache=cl, pos=pos, q_chunk=1,
                              cache_slice_window=csw,
                              decode_kernel=decode_kernel)
            for k, v in nc.items():
                new_cache[k] = new_cache[k].at[i].set(v.astype(
                    new_cache[k].dtype))
        cache = new_cache
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0, :],
                        lm_head_weight(params, cfg).astype(x.dtype))
    return logits, cache
