"""jit'd public wrappers around the Pallas kernels.

On the CPU kernels run with interpret=True; on a real TPU
backend the same call sites compile to Mosaic. ``use_pallas()`` central-
switches; model code goes through these wrappers only where the kernel is
profitable (full-seq attention, the NBL block GEMM, covariance updates).
Shapes are padded to block multiples here so kernels stay assert-simple.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cov_accum import cov_accum
from repro.kernels.flash_attention import flash_attention
from repro.kernels.nbl_linear import nbl_linear


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "softcap", "block_q", "block_k",
    "interpret"))
def attention(q, k, v, *, scale: Optional[float] = None, causal: bool = True,
              window: Optional[int] = None, softcap: Optional[float] = None,
              block_q: int = 128, block_k: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention with seq/head-dim padding to kernel block multiples."""
    interpret = (not on_tpu()) if interpret is None else interpret
    s, t, d = q.shape[2], k.shape[2], q.shape[3]
    qp, _ = _pad_to(q, 2, block_q)
    kp, _ = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    # pad head_dim to the 128-lane register width
    qp, _ = _pad_to(qp, 3, 128)
    kp, _ = _pad_to(kp, 3, 128)
    vp, _ = _pad_to(vp, 3, 128)
    scale = d ** -0.5 if scale is None else scale  # scale by TRUE head dim
    # padded K positions are masked out by causal/window iff they are in the
    # future of every query; with right-padding kpos >= t > qpos, causal
    # masking handles it. Non-causal callers must pass exact multiples.
    assert causal or (t % block_k == 0 and s % block_q == 0)
    out = flash_attention(qp, kp, vp, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out[:, :, :s, :d]


@functools.partial(jax.jit, static_argnames=("residual", "interpret"))
def nbl_apply(x, w, b, *, residual: bool = True,
              interpret: Optional[bool] = None) -> jax.Array:
    """NBL replacement block on (B, S, d) activations."""
    interpret = (not on_tpu()) if interpret is None else interpret
    bsz, s, d = x.shape
    xt = x.reshape(bsz * s, d)
    xt, m = _pad_to(xt, 0, 256)
    out = nbl_linear(xt, w, b, residual=residual, interpret=interpret)
    return out[:m].reshape(bsz, s, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cov_update(acc, x, y=None, *, interpret: Optional[bool] = None):
    """acc += yᵀx on (T, D) token blocks (y=None → Gram update)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    xt, _ = _pad_to(x, 0, 512)      # zero rows contribute nothing
    yt = None if y is None else _pad_to(y, 0, 512)[0]
    return cov_accum(acc, xt, yt, interpret=interpret)


# re-exported oracles
attention_ref = ref.flash_attention_ref
nbl_apply_ref = ref.nbl_linear_ref
cov_update_ref = ref.cov_accum_ref
