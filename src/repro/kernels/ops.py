"""jit'd public wrappers around the Pallas kernels.

Handles layout adaptation (model layouts <-> kernel layouts), padding to
block multiples, and backend dispatch (:func:`interpret_mode`): on the CPU backend the
kernels execute in ``interpret=True`` mode (used by all tests); on a TPU
they lower to Mosaic; any other backend raises.  ``force_interpret``
pins the mode (tests interpret; the v5e compile tests lower).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import mlstm_scan as _ml
from . import quant_blockwise as _qb
from . import rglru_scan as _rg


def interpret_mode(force: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: ``force`` when
    given, else True on the CPU backend and False on a TPU.  Any other
    backend raises — interpret mode must never hide a missing chip."""
    if force is not None:
        return force
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas lowering for backend {backend!r}; "
                       f"the kernels run on a TPU or interpreted on CPU")


# ---------------------------------------------------------------------------
# Attention (model layout: q/k/v (B, S, H, Dh) flat heads)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mode", "window", "chunk",
                                             "force_interpret"))
def flash_attention(q, k, v, *, mode: str = "causal", window: int = 0,
                    chunk: int = 0, force_interpret: bool | None = None):
    B, S, H, Dh = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], Dh)
    out = _fa.flash_attention(
        fold(q), fold(k), fold(v), mode=mode, window=window, chunk=chunk,
        qb=min(256, S), kb=min(256, k.shape[1]),
        interpret=interpret_mode(force_interpret))
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# RG-LRU scan (model layout: a/b (B, S, W), h0 (B, W))
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("force_interpret",))
def rglru_scan(a, b, h0, *, force_interpret: bool | None = None):
    B, S, W = a.shape
    return _rg.rglru_scan(a, b, h0, bb=min(8, B), sb=min(256, S),
                          wb=min(128, W),
                          interpret=interpret_mode(force_interpret))


# ---------------------------------------------------------------------------
# mLSTM (model layout: q/k/v (B, H, S, Dh); li/lf (B, H, S))
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "force_interpret"))
def mlstm_scan(q, k, v, li, lf, *, chunk: int = 256,
               force_interpret: bool | None = None):
    B, H, S, Dh = q.shape
    fold = lambda t: t.reshape(B * H, S, Dh)
    fold2 = lambda t: t.reshape(B * H, S)
    out = _ml.mlstm_scan(fold(q), fold(k), fold(v), fold2(li), fold2(lf),
                         chunk=min(chunk, S),
                         interpret=interpret_mode(force_interpret))
    return out.reshape(B, H, S, Dh)


# ---------------------------------------------------------------------------
# Blockwise int8 quantization (arbitrary arrays)
# ---------------------------------------------------------------------------

#: row block of the quantize kernels: a multiple of 32, the int8 sublane
#: tile of a TPU.
_QUANT_ROWS = 256


def _pad_of(size: int) -> tuple:
    """(pad, D): ``size + pad`` elements as ``(rows, D)``, where ``rows``
    is either at most one row block (the block is then the whole array)
    or a multiple of it, so every block is tile-aligned on a TPU."""
    D = 512 if size >= 512 else 128
    rows = -(-size // D)
    if rows > _QUANT_ROWS:
        rows = -(-rows // _QUANT_ROWS) * _QUANT_ROWS
    return rows * D - size, D


@functools.partial(jax.jit, static_argnames=("pad", "D", "force_interpret"))
def _quantize_2d(x, *, pad: int, D: int, force_interpret: bool | None):
    flat = x.reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    x2 = flat.reshape(-1, D)
    return _qb.quantize(x2, bn=min(_QUANT_ROWS, x2.shape[0]),
                        interpret=interpret_mode(force_interpret))


def quantize_array(x, *, force_interpret: bool | None = None):
    """Quantize ANY-shaped array; returns (int8 2-D payload, scales, pad)."""
    pad, D = _pad_of(x.size)
    q, s = _quantize_2d(x, pad=pad, D=D, force_interpret=force_interpret)
    return q, s, pad


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "pad",
                                             "force_interpret"))
def dequantize_array(q, s, *, shape, dtype, pad: int,
                     force_interpret: bool | None = None):
    x2 = _qb.dequantize(q, s, dtype=jnp.dtype(dtype),
                        bn=min(_QUANT_ROWS, q.shape[0]),
                        interpret=interpret_mode(force_interpret))
    flat = x2.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)
