"""Pallas TPU kernel for chunked Gated Linear Attention — the shared
compute hot-spot of the hymba SSD branch and the xLSTM mLSTM blocks
(EXPERIMENTS.md §Perf identified its autodiff residuals as the dominant
memory term of hybrid/ssm training; the jnp-level fix is chunk-remat, the
kernel-level fix is this: intra-chunk tiles never leave VMEM).

Recurrence (repro.models.ssm.gla_chunked semantics):

    H_t = exp(ld_t) · H_{t-1} + exp(li_t) · k_t ⊗ v_t
    y_t = q_t · H_t

Grid: (batch·heads, n_chunks) — sequential "arbitrary" order. The running
state H [N, P] lives in a VMEM scratch buffer, carried across the chunk
dimension exactly like the PSO fused kernel carries gbest (DESIGN.md §2:
TPU sequential-grid semantics replace cross-block synchronization). Per
step the kernel computes the intra-chunk masked matmul in registers/VMEM
and writes only the [L, P] output tile — the [L, L] weight tile is never
materialized to HBM.

Forward only (training backward uses the chunk-remat path; a custom
backward kernel is symmetric future work). Validated in interpret mode
against the pure-jnp engine in tests/test_gla_kernel.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pso_step import default_interpret

_CLAMP = 20.0


def _gla_kernel(q_ref, k_ref, v_ref, ld_ref, li_ref, y_ref, h_scratch,
                *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _reset():                       # new (batch, head): zero the state
        h_scratch[...] = jnp.zeros_like(h_scratch)

    q = q_ref[0]                        # [L, N]
    k = k_ref[0]
    v = v_ref[0]                        # [L, P]
    ld = ld_ref[0].astype(jnp.float32)  # [L]
    li = li_ref[0].astype(jnp.float32)
    cum = jnp.cumsum(ld)                # [L]
    idx = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = idx >= jdx
    logw = cum[:, None] - cum[None, :] + li[None, :]
    logw = jnp.where(tri, logw, -jnp.inf)
    w = jnp.exp(jnp.clip(logw, -_CLAMP * 4, _CLAMP))        # [L, L]
    qk = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    y_intra = jnp.dot((qk * w).astype(v.dtype), v,
                      preferred_element_type=jnp.float32)   # [L, P]
    ei = jnp.exp(jnp.clip(cum, -_CLAMP * 4, _CLAMP))        # [L]
    h = h_scratch[...]
    y_inter = jnp.dot((q * ei[:, None]).astype(jnp.float32),
                      h, preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update: H <- e_tot·H + Σ_j e(tot-cum_j+li_j) k_j ⊗ v_j
    tot = cum[-1]
    wj = jnp.exp(jnp.clip(tot - cum + li, -_CLAMP * 4, _CLAMP))
    dstate = jnp.dot((k * wj[:, None]).T.astype(jnp.float32),
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)    # [N, P]
    e_tot = jnp.exp(jnp.clip(tot, -_CLAMP * 4, _CLAMP))
    h_scratch[...] = h * e_tot + dstate


def gla_forward_call(bh: int, s: int, n: int, p: int, chunk: int, dtype,
                     interpret=None):
    """Build the pallas_call. Inputs: q,k [BH,S,N]; v [BH,S,P]; ld,li
    [BH,S]. Returns y [BH,S,P]. S must be a multiple of chunk."""
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kern = functools.partial(_gla_kernel, chunk=chunk)
    mat = lambda width: pl.BlockSpec((1, chunk, width),
                                     lambda b, c: (b, c, 0))
    vec = pl.BlockSpec((1, chunk), lambda b, c: (b, c))
    return pl.pallas_call(
        kern,
        grid=(bh, nc),
        in_specs=[mat(n), mat(n), mat(p), vec, vec],
        out_specs=mat(p),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY)),
        interpret=default_interpret() if interpret is None else interpret,
        name="gla_chunked_fwd",
    )


def gla_forward(q, k, v, log_decay, log_inc, chunk: int = 128):
    """Drop-in (forward-only) replacement for models.ssm.gla_chunked.

    q,k: [B,S,H,N]; v: [B,S,H,P]; gates [B,S,H]. Returns y [B,S,H,P].
    """
    b, s, h, n = q.shape
    p = v.shape[-1]
    pad = (-s) % chunk
    if pad:
        zp = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v = zp(q), zp(k), zp(v)
        log_decay = jnp.pad(log_decay, [(0, 0), (0, pad), (0, 0)])
        log_inc = jnp.pad(log_inc, [(0, 0), (0, pad), (0, 0)],
                          constant_values=-_CLAMP * 2)
    sp = s + pad
    fold = lambda a: a.transpose(0, 2, 1, *range(3, a.ndim)).reshape(
        b * h, sp, *a.shape[3:])
    qf, kf, vf = fold(q), fold(k), fold(v)
    ldf = log_decay.transpose(0, 2, 1).reshape(b * h, sp)
    lif = log_inc.transpose(0, 2, 1).reshape(b * h, sp)
    call = gla_forward_call(b * h, sp, n, p, min(chunk, sp), v.dtype)
    y = call(qf, kf, vf, ldf, lif)
    y = y.reshape(b, h, sp, p).transpose(0, 2, 1, 3)
    return y[:, :s]
