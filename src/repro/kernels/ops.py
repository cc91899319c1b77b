"""Jit'd public wrappers around the Pallas kernels.

The backend decides how a kernel runs: on TPU it lowers to Mosaic, on CPU
it runs in interpret mode (Pallas executes the kernel body in Python,
validating the exact TPU program; this is how the tests run). Any other
backend raises rather than silently interpreting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.kd_loss import kd_loss_pallas
from repro.kernels.kd_loss import kd_loss_rows as _kd_loss_rows
from repro.kernels.ssd_scan import ssd_decode_step_pallas, ssd_scan_pallas
from repro.kernels.swa_attention import (extent_decode_attend_pallas,
                                         ring_decode_attend_pallas,
                                         swa_attention_pallas)


def _interpret() -> bool:
    """Interpret mode exactly on the CPU backend; Mosaic on TPU."""
    backend = jax.default_backend()
    if backend in ("cpu", "tpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"Pallas kernels lower only for TPU (or interpret on CPU); the "
        f"default backend is {backend!r}")


# Module-level kernel leaf wrappers: one jit per op for the whole process,
# compile keys are the declared static_argnames — already the discipline
# JitCache enforces, with no donation or entry-point multiplexing to pool.
# repro-lint: disable=R1
@functools.partial(jax.jit, static_argnames=("alpha", "temperature"))
def kd_loss(student_logits, teacher_logits, labels, alpha: float,
            temperature: float = 1.0):
    """Mean fused KD loss over all rows (α·CE + (1-α)·Σ((s-t)/T)²)."""
    R = 1
    for dim in student_logits.shape[:-1]:
        R *= dim
    V = student_logits.shape[-1]
    per_row = kd_loss_pallas(student_logits.reshape(R, V),
                             teacher_logits.reshape(R, V),
                             labels.reshape(R), alpha,
                             temperature=temperature,
                             interpret=_interpret())
    return jnp.mean(per_row)


# Not jitted (like the decode-step kernels below): this is the loss leaf of
# the distillation engine's scan programs, which its JitCache compiles as a
# whole — a nested module-level jit would fragment that cache. The analytic
# custom_vjp makes it a drop-in for value_and_grad inside those programs.
def kd_loss_rows(student_logits, teacher_logits, labels, alpha: float,
                 temperature: float = 1.0, valid=None):
    """Differentiable per-row fused KD loss; (R, V) in, (R,) f32 out.

    Masked rows (``valid`` == 0) produce exactly-zero loss and gradients.
    """
    return _kd_loss_rows(student_logits, teacher_logits, labels, alpha,
                         temperature=temperature, valid=valid,
                         interpret=_interpret())


# repro-lint: disable=R1  (see kd_loss note above)
@functools.partial(jax.jit, static_argnames=("window", "causal"))
def swa_attention(q, k, v, window: int, causal: bool = True):
    """(BH, S, D) sliding-window flash attention; window=0 -> full."""
    S = q.shape[1]
    w = window if window > 0 else S
    return swa_attention_pallas(q, k, v, w, causal=causal,
                                q_block=min(128, S), k_block=min(128, S),
                                interpret=_interpret())


# repro-lint: disable=R1  (see kd_loss note above)
@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 128):
    """Mamba2 SSD layer core. See ssd_scan_pallas."""
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk,
                           interpret=_interpret())


# Decode-step kernels are NOT jitted here: they run inside the serving
# decode programs, which JitCache compiles as a whole (one program per
# ladder rung) — a nested module-level jit would fragment that cache.
def ring_decode_attend(q, k, v, pos, window):
    """Fused one-token SWA attend over a W-slot ring cache.

    q: (B, KV, G, D); k/v: (B, W, KV, D); pos/window traced int32
    scalars.  Modular slot->position mapping and window masking happen
    inside the kernel (one HBM pass over the ring)."""
    return ring_decode_attend_pallas(q, k, v, pos, window,
                                     interpret=_interpret())


def extent_decode_attend(q, k, v, pos, window, k_ext: int):
    """Fused one-token attend over the first ``k_ext`` cache positions.

    q: (B, KV, G, D); k/v: (B, S_max, KV, D); static ``k_ext`` bounds the
    HBM read via the BlockSpec — the ladder-bucketed decode program only
    streams the live prefix of the uniform cache."""
    return extent_decode_attend_pallas(q, k, v, pos, window, k_ext,
                                       interpret=_interpret())


def ssd_decode_step(xh, dt, A, Bm, Cm, state):
    """Fused one-token SSD recurrence (decay + rank-1 update + readout)."""
    return ssd_decode_step_pallas(xh, dt, A, Bm, Cm, state,
                                  interpret=_interpret())


# re-export oracles for convenience
kd_loss_ref = ref.kd_loss_ref
swa_attention_ref = ref.swa_attention_ref
ssd_scan_ref = ref.ssd_scan_ref
ssd_sequential_ref = ref.ssd_sequential_ref
