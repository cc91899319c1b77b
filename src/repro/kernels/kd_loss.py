"""Fused KD-loss Pallas TPU kernel: α·CE(student, labels) + (1-α)·Σ((s-t)/T)².

Motivation (DESIGN.md §3): the KD tail is memory-bound — a naive
implementation reads the student logits for max, exp-sum, gather and the
squared error separately, and reads the teacher logits twice. This kernel
streams both logit tensors through VMEM exactly once, carrying the online
logsumexp (m, l), the gathered gold logit, and the running squared error in
VMEM scratch across vocab tiles.

Grid = (row_blocks, vocab_tiles); the vocab tile index is innermost so the
scratch accumulators live across the sweep of one row block.

Two additions serve the batched distillation engine (core/distill.py):

- ``temperature`` scales the logit-matching term to Σ((s-t)/T)² — T=1 is
  the paper's plain MSE-on-logits; extreme T exercises the accumulator's
  numerics (the parity tests sweep T→0⁺ and T≫1).
- ``valid`` is a per-row float mask: rows with valid == 0 produce *exactly*
  0.0 (a ``where``-select, never ``0·x``, so garbage rows — padding from
  the masked-scan engine — cannot leak NaN/Inf into the output).

``kd_loss_rows`` wraps the kernel in a ``jax.custom_vjp`` with the analytic
backward (Pallas kernels have no general autodiff rule), making the fused
kernel a drop-in loss for ``jax.value_and_grad`` inside the distillation
scan programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Mosaic tiles the last two dims of every block by (8, 128): a block dim
# must be a multiple of its tile or span the whole array dim. Per-row
# values (labels, the valid mask, the output and the accumulators) are
# therefore (rows, 1) columns, and rows pad to a multiple of _ROW_BLOCK.
_ROW_BLOCK = 8
_VOCAB_BLOCK = 512


def _kernel(s_ref, t_ref, lab_ref, v_ref, out_ref,
            m_ref, l_ref, gold_ref, sq_ref,
            *, alpha: float, inv_t: float, vb: int, num_vt: int, vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        gold_ref[...] = jnp.zeros_like(gold_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    s = s_ref[...].astype(jnp.float32)              # (rb, vb)
    t = t_ref[...].astype(jnp.float32)
    lab = lab_ref[...]                              # (rb, 1)
    rb = s.shape[0]

    # mask out padding columns of the last tile
    col = j * vb + jax.lax.broadcasted_iota(jnp.int32, (rb, vb), 1)
    valid = col < vocab
    s_m = jnp.where(valid, s, -1e30)

    # online logsumexp
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_m, axis=-1, keepdims=True))
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.where(valid, jnp.exp(s_m - m_new), 0.0),
                  axis=-1, keepdims=True)
    m_ref[...] = m_new

    # gold logit gather (label may fall in this tile)
    hit = col == lab
    gold_ref[...] += jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True)

    # running squared error (zero on padding), temperature-scaled
    diff = jnp.where(valid, (s - t) * inv_t, 0.0)
    sq_ref[...] += jnp.sum(diff * diff, axis=-1, keepdims=True)

    @pl.when(j == num_vt - 1)
    def _done():
        ce = jnp.log(l_ref[...]) + m_ref[...] - gold_ref[...]
        loss = alpha * ce + (1.0 - alpha) * sq_ref[...]
        # select, never multiply: masked rows must be exactly 0.0 even
        # when their (garbage) logits produced NaN/Inf accumulators
        out_ref[...] = jnp.where(v_ref[...] > 0.0, loss, 0.0)


def kd_loss_pallas(student_logits, teacher_logits, labels, alpha: float,
                   temperature: float = 1.0, valid=None,
                   interpret: bool = True):
    """Per-row fused loss. student/teacher: (R, V); labels (R,) int32.

    Returns (R,) float32. Rows are padded to a multiple of the row block;
    vocab tiles are masked in-kernel so any (R, V) works. ``valid`` (R,)
    marks live rows (None = all live); masked rows return exactly 0.0.
    ``alpha`` and ``temperature`` are trace-time statics.
    """
    R, V = student_logits.shape
    if valid is None:
        valid = jnp.ones((R,), jnp.float32)
    valid = valid.astype(jnp.float32)
    rb = _ROW_BLOCK
    pad_r = (-R) % rb
    if pad_r:
        student_logits = jnp.pad(student_logits, ((0, pad_r), (0, 0)))
        teacher_logits = jnp.pad(teacher_logits, ((0, pad_r), (0, 0)))
        labels = jnp.pad(labels, (0, pad_r))
        valid = jnp.pad(valid, (0, pad_r))          # pad rows are invalid
    Rp = R + pad_r
    vb = min(_VOCAB_BLOCK, V)
    num_vt = pl.cdiv(V, vb)
    pad_v = num_vt * vb - V
    if pad_v:
        student_logits = jnp.pad(student_logits, ((0, 0), (0, pad_v)))
        teacher_logits = jnp.pad(teacher_logits, ((0, 0), (0, pad_v)))

    # alpha/temperature are declared static at the jit boundaries that
    # wrap this call (ops.kd_loss, the distill engine's dcfg fields),
    # so these float() are trace-time constants, not device syncs.
    alpha_c = float(alpha)                # repro-lint: disable=R2
    inv_t = 1.0 / float(temperature)      # repro-lint: disable=R2
    row = pl.BlockSpec((rb, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha_c, inv_t=inv_t, vb=vb,
                          num_vt=num_vt, vocab=V),
        grid=(Rp // rb, num_vt),
        in_specs=[
            pl.BlockSpec((rb, vb), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vb), lambda i, j: (i, j)),
            row,
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rb, 1), jnp.float32),   # running max m
            pltpu.VMEM((rb, 1), jnp.float32),   # running sumexp l
            pltpu.VMEM((rb, 1), jnp.float32),   # gold logit
            pltpu.VMEM((rb, 1), jnp.float32),   # running Σ((s-t)/T)²
        ],
        interpret=interpret,
    )(student_logits, teacher_logits, labels.reshape(Rp, 1),
      valid.reshape(Rp, 1))
    return out[:R, 0]


# ---------------------------------------------------------------------------
# Differentiable wrapper: Pallas forward, analytic backward
# ---------------------------------------------------------------------------
#
#   L_r = α·(logsumexp(s_r) - s_r[y_r]) + (1-α)·Σ_v ((s_rv - t_rv)/T)²
#   ∂L_r/∂s = α·(softmax(s_r) - onehot(y_r)) + 2(1-α)(s_r - t_r)/T²
#   ∂L_r/∂t = -2(1-α)(s_r - t_r)/T²
#
# masked rows get exactly-zero cotangents (where-select, so garbage logits
# in padded rows cannot NaN-poison the gradients either).

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rows_vjp(alpha, temperature, interpret, s, t, labels, valid):
    return kd_loss_pallas(s, t, labels, alpha, temperature=temperature,
                          valid=valid, interpret=interpret)


def _rows_fwd(alpha, temperature, interpret, s, t, labels, valid):
    out = _rows_vjp(alpha, temperature, interpret, s, t, labels, valid)
    return out, (s, t, labels, valid)


def _rows_bwd(alpha, temperature, interpret, res, g):
    s, t, labels, valid = res
    s32 = s.astype(jnp.float32)
    t32 = t.astype(jnp.float32)
    p = jax.nn.softmax(s32, axis=-1)
    onehot = jax.nn.one_hot(labels, s.shape[-1], dtype=jnp.float32)
    dsq = (2.0 / (temperature * temperature)) * (s32 - t32)
    live = (valid > 0.0)[:, None]
    gcol = g[:, None]
    ds = jnp.where(live, gcol * (alpha * (p - onehot)
                                 + (1.0 - alpha) * dsq), 0.0)
    dt = jnp.where(live, gcol * (-(1.0 - alpha)) * dsq, 0.0)
    return ds.astype(s.dtype), dt.astype(t.dtype), None, None


_rows_vjp.defvjp(_rows_fwd, _rows_bwd)


def kd_loss_rows(student_logits, teacher_logits, labels, alpha: float,
                 temperature: float = 1.0, valid=None,
                 interpret: bool = True):
    """Differentiable per-row fused KD loss (grad flows to both logit
    tensors; labels/valid are non-differentiable). Same shapes and masking
    semantics as ``kd_loss_pallas``."""
    R = student_logits.shape[0]
    if valid is None:
        valid = jnp.ones((R,), jnp.float32)
    return _rows_vjp(alpha, temperature, interpret,
                     student_logits, teacher_logits,
                     labels.astype(jnp.int32), valid.astype(jnp.float32))
