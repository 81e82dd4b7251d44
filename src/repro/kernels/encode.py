"""Pallas TPU kernel: single-stage Huffman symbol→(code, length) mapping.

This is the paper's critical-path stage — the ONLY stage, hence
"single-stage".  Each uint8 symbol is looked up in a fixed 256-entry
codebook LUT; downstream bit-packing consumes the (code, length) pairs.

TPU adaptation: a byte→word table lookup is a random gather, which the
TPU vector unit handles poorly.  We reformulate the LUT as a matmul on
the MXU.  For each 128-symbol row of the block, a (256, 128) one-hot
(bins on sublanes, symbols on lanes) is built from a sublane-iota
compare, and the transposed LUT ``(8, 256) @ one-hot`` yields an
(8, 128) slab whose rows are [code low byte, code high byte, length].
Codes are length-limited to ≤16 bits (package-merge), so every LUT
entry is a byte ≤ 255 — exact in bfloat16 — and each output is a single
product summed in f32: the matmul is exact.  No reshape moves symbols
between lanes and sublanes.  This is the TPU-idiomatic form of a small
LUT and the kernel the hardware encoder in the paper would replace.

Per grid step the kernel also adds the block's code lengths into a
sequential (1, 128) per-lane accumulator — the wire-size term the
collective ledger needs, produced without a second pass over the data
(that's the point of the paper: no extra scans).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

N_SYMBOLS = 256
ROWS = 32
LANES = 128
BLOCK = ROWS * LANES
LUT_ROWS = 8            # [code lo byte, code hi byte, length, 0 × 5]


def _encode_kernel(sym_ref, lut_ref, code_ref, len_ref, bits_ref):
    """Map a (ROWS, LANES) symbol block through the codebook LUT.

    sym_ref:  (ROWS, LANES) int32 — symbols
    lut_ref:  (8, 256) bf16 — transposed byte-split LUT (rows 0..2 used)
    code_ref: (ROWS, LANES) int32 out — codewords
    len_ref:  (ROWS, LANES) int32 out — code lengths
    bits_ref: (1, LANES) int32 out — per-lane running bit totals
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        bits_ref[...] = jnp.zeros_like(bits_ref)

    bins = jax.lax.broadcasted_iota(jnp.int32, (N_SYMBOLS, LANES), 0)
    lut = lut_ref[...]

    def row(r, bits):
        onehot = jnp.where(sym_ref[pl.ds(r, 1), :] == bins, 1.0, 0.0
                           ).astype(jnp.bfloat16)              # (256, L)
        slab = jnp.dot(lut, onehot,
                       preferred_element_type=jnp.float32)     # (8, L) MXU
        slab = slab.astype(jnp.int32)
        code_ref[pl.ds(r, 1), :] = slab[0:1, :] + (slab[1:2, :] << 8)
        len_ref[pl.ds(r, 1), :] = slab[2:3, :]
        return bits + slab[2:3, :]

    bits_ref[...] = jax.lax.fori_loop(0, ROWS, row, bits_ref[...])


def lut_rows(codes: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """A book's (n ≤ 256,) codewords and lengths → the kernels' (8, 256)
    bf16 byte-split transposed LUT; columns past n stay 0.  Every entry
    is a byte (codes ≤ 16 bits), hence exact in bf16."""
    code = codes.astype(jnp.int32)
    rows = jnp.stack([code & 0xFF, code >> 8, lengths.astype(jnp.int32)])
    out = jnp.zeros((LUT_ROWS, N_SYMBOLS), jnp.float32)
    return out.at[:3, :code.shape[0]].set(rows.astype(jnp.float32)
                                          ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_lookup_pallas(symbols: jnp.ndarray, lut: jnp.ndarray, *,
                         interpret: bool | None = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-stage LUT pass: symbols (N,) uint8/int32, lut (256, 2) f32/u32.

    Returns (codes (N,) uint32, lengths (N,) int32, total_bits () int32).
    Padding symbols are 0; their contribution to total_bits is subtracted
    exactly (pad count × len(lut[0])).
    """
    n = symbols.size
    sym = symbols.reshape(-1).astype(jnp.int32)
    n_blocks = max((n + BLOCK - 1) // BLOCK, 1)
    pad = n_blocks * BLOCK - n
    sym = jnp.pad(sym, (0, pad)).reshape(n_blocks * ROWS, LANES)

    codes, lens, bits = pl.pallas_call(
        _encode_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((LUT_ROWS, N_SYMBOLS), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks * ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(sym, lut_rows(lut[:, 0], lut[:, 1]))

    total_bits = bits.sum()
    if pad:
        total_bits = total_bits - pad * lens.reshape(-1)[-1]
    codes = codes.reshape(-1)[:n].astype(jnp.uint32)
    lens = lens.reshape(-1)[:n]
    return codes, lens, total_bits
