"""Jit'd public API over the xnor_gemm kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.xnor_gemm import kernel as _k

WORD_BITS = 32


def pack_pm1(bits: jnp.ndarray) -> jnp.ndarray:
    """(R, K) {0,1} bits -> (R, ceil(K/32)) int32, K packed LSB-first."""
    r, k = bits.shape
    kw = -(-k // WORD_BITS)
    b = jnp.pad(bits.astype(jnp.uint32), ((0, 0), (0, kw * WORD_BITS - k)))
    chunks = b.reshape(r, kw, WORD_BITS)
    weights = (jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32))
    return (chunks * weights).sum(axis=-1, dtype=jnp.uint32).astype(jnp.int32)


def _pad_rows(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _pad_cols(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[1]) % mult
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def xnor_gemm(a_bits: jnp.ndarray, b_bits: jnp.ndarray, *, bm: int = 128,
              bn: int = 128, bk: int = 128, interpret: bool | None = None
              ) -> jnp.ndarray:
    """Binarized +-1 GEMM: a (M, K) {0,1} x b (N, K) {0,1} -> (M, N) int32."""
    m, k = a_bits.shape
    n, k2 = b_bits.shape
    if k != k2:
        raise ValueError(f"K mismatch: {k} vs {k2}")
    ap = _pad_cols(_pad_rows(pack_pm1(a_bits), bm), bk)
    bp = _pad_cols(_pad_rows(pack_pm1(b_bits), bn), bk)
    out = _k.xnor_gemm_pallas(ap, bp, k_bits=k, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)
    return out[:m, :n]
