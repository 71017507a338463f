"""Pallas TPU kernel: XNOR + popcount GEMM (binarized matmul).

The paper's XNOR baseline (§8.3) replaces FINN's LUT-based XNOR unit with a
DSP-based one inside the MVTU. On TPU the same op is a K-bitpacked GEMM:

    dot_{+-1}(a, b) = K - 2 * popcount(a_packed XOR b_packed)

Tiling: grid (M/bm, N/bn, Kw/bk); per step the kernel XORs a (bm, bk) slab
of packed activations against a (bn, bk) slab of packed weights, reduces
popcounts along bk into an int32 (bm, bn) VMEM accumulator. The K grid axis
is innermost so Mosaic pipelines the HBM->VMEM slab DMAs (double buffering)
against the VPU popcount reduction — the same overlap discipline as the
paper's burst/double-buffer design.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _xnor_kernel(a_ref, b_ref, out_ref, acc_ref, *, k_bits: int, n_kw: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                                   # (bm, bk) int32
    b = b_ref[...]                                   # (bn, bk) int32
    x = jax.lax.population_count(
        (a[:, None, :] ^ b[None, :, :]).astype(jnp.uint32)).astype(jnp.int32)
    acc_ref[...] += x.sum(axis=-1)

    @pl.when(pl.program_id(2) == n_kw - 1)
    def _done():
        # dot = K - 2 * hamming
        out_ref[...] = jnp.int32(k_bits) - 2 * acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("k_bits", "bm", "bn", "bk", "interpret"))
def xnor_gemm_pallas(a_packed: jnp.ndarray, b_packed: jnp.ndarray, *,
                     k_bits: int, bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool | None = None) -> jnp.ndarray:
    """a_packed: (M, Kw) int32; b_packed: (N, Kw) int32 -> (M, N) int32.

    M % bm == N % bn == Kw % bk == 0 (caller pads). Mosaic wants a
    lane-dense ``bk`` (a multiple of 128 words) and ``bn`` (the output
    block's lanes); the interpreter takes any. Zero-padding BOTH
    operands' K-words is safe: pad XOR pad = 0 contributes nothing to the
    hamming count, and ``k_bits`` counts only real bits.
    """
    m, kw = a_packed.shape
    n, _ = b_packed.shape
    grid = (m // bm, n // bn, kw // bk)
    return pl.pallas_call(
        functools.partial(_xnor_kernel, k_bits=k_bits, n_kw=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(a_packed, b_packed)
