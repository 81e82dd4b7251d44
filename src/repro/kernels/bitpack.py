"""Pallas TPU kernel: block-local codebook lookup and bit-packing.

Completes the on-device encode pipeline: histogram (observe) → LUT
(single-stage map) and **pack** (this kernel, both stages in one pass).
Global variable-length packing is inherently sequential at the bit
level, so we split it the way a link-layer encoder does:

  * each BLOCK of symbols is looked up and packed into its own
    word-aligned sub-stream entirely in VMEM;
  * the tiny merge of per-block streams (one barrel shift per block) is
    the transmit-FIFO stitch; it runs on host / in jnp
    (`ops.merge_block_streams`) and is O(output words).

TPU form.  A block is a (16, 128) lane-dense slab of int32 symbols.
Lookup first, as in ``encode.py``: each 128-symbol row is compared with
a (256, 128) sublane iota of bins, and the book's byte-split transposed
LUT ``(8, 256) @ one-hot`` on the MXU gives [code low byte, code high
byte, length] — LUT entries are bytes (codes ≤ 16 bits), exact in bf16,
and each output is one product, so the lookup is exact.  A slot holding
``NO_SYMBOL`` (256) matches no bin and gets code 0, length 0.

Bit offsets come from an in-row inclusive prefix sum (a (128, 128)
upper-triangular matmul — lengths ≤ 16, so every partial sum is exact
in f32) plus a running per-row offset.  A code of ≤ 16 bits at offset o
spans word ``o >> 5`` (its high part) and possibly the next (its low
part).  There is no vector scatter on the TPU, so the "scatter" is a
matmul too: for each row, the byte planes of the high and low parts,
(8, 128), are contracted over the row's 128 codes with a (WIN, 128)
one-hot of their word index within a window.  A row's 128 codes of ≤ 16
bits touch at most 65 words from ``run >> 5`` on (``run``: the bits
before the row), so a window of WIN = 256 words from the 128-aligned
``base = (run >> 5) & ~127`` holds them all, and ``base + WIN ≤
CAP_PAD`` for every row of a block; each row's (8, WIN) product is
added into the block's accumulator at ``base``.  Codes own disjoint bit
fields, so every byte of every word is a sum of disjoint fields ≤ 255 —
exact in bf16 inputs and f32 accumulation.  The low parts land one word
later (a lane roll).

Per-block capacity is BLOCK × MAX_CODE_LEN bits; the block's true bit
count rides in a side output so the merge drops the slack.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.huffman import MAX_CODE_LEN
from .encode import LUT_ROWS, N_SYMBOLS, lut_rows
from .platform import resolve_interpret

BLOCK = 2048
CAP_WORDS = BLOCK * MAX_CODE_LEN // 32 + 1      # +1 pad word
LANES = 128
ROWS = BLOCK // LANES                           # 16 rows of 128 codes
CAP_PAD = -(-CAP_WORDS // LANES) * LANES        # lane-aligned word slab
WIN = 2 * LANES                                 # words one row can touch
G = 8                                           # blocks per grid step
NO_SYMBOL = N_SYMBOLS                           # matches no LUT bin

_srl = jax.lax.shift_right_logical


def _pack_kernel(sym_ref, lut_ref, words_ref, bits_ref, code_ref, len_ref,
                 incl_ref, acc_ref, *, g_step: int):
    """Look up and pack g_step blocks.  sym: (g_step·ROWS, LANES) int32
    (NO_SYMBOL → no code); lut: (8, 256) bf16 ``encode.lut_rows``;
    code/len/incl_ref: (ROWS, LANES) int32 and acc_ref: (8, CAP_PAD)
    float32 scratch.  The lookup is its own unrolled pass over a block's
    rows (on a v5e it packs a plane in 2.2 ms, against 3.9 ms with the
    lookup inside the packing row loop)."""
    a = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    tri = jnp.where(a <= b, 1.0, 0.0).astype(jnp.bfloat16)
    win_iota = jax.lax.broadcasted_iota(jnp.int32, (WIN, LANES), 0)
    plane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    bins = jax.lax.broadcasted_iota(jnp.int32, (N_SYMBOLS, LANES), 0)
    lut = lut_ref[...]

    def lookup(i):
        """(8, LANES) f32 [code lo byte, code hi byte, length, 0 ...] of
        symbol row i: one product per output, exact."""
        onehot = jnp.where(sym_ref[pl.ds(i, 1), :] == bins, 1.0, 0.0)
        return jnp.dot(lut, onehot.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    def block(g, carry):
        first = pl.multiple_of(g * ROWS, ROWS)

        def look(r, c):
            slab = lookup(first + r).astype(jnp.int32)
            code_ref[pl.ds(r, 1), :] = slab[0:1] | (slab[1:2] << 8)
            len_ref[pl.ds(r, 1), :] = slab[2:3]
            return c

        jax.lax.fori_loop(0, ROWS, look, 0, unroll=True)
        incl_ref[...] = jnp.dot(len_ref[...].astype(jnp.bfloat16), tri,
                                preferred_element_type=jnp.float32
                                ).astype(jnp.int32)   # in-row prefix sums
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def row(r, run):                              # run: bits before row
            v = code_ref[pl.ds(r, 1), :]
            l = len_ref[pl.ds(r, 1), :]
            incl = incl_ref[pl.ds(r, 1), :]
            offs = run + incl - l                     # exclusive offsets
            pos = offs & 31
            sh = 32 - pos - l
            hi = jnp.where(sh >= 0, v << jnp.clip(sh, 0, 31),
                           v >> jnp.clip(-sh, 0, 31))
            lo = jnp.where(sh < 0, v << jnp.clip(32 + sh, 0, 31), 0)
            part = jnp.where(plane < 4, hi, lo)       # (8, L) byte planes
            byte = _srl(part, (plane & 3) * 8) & 0xFF
            base = pl.multiple_of((run >> 5) & ~(LANES - 1), LANES)
            onehot = jnp.where(win_iota == (offs >> 5) - base, 1.0, 0.0)
            acc_ref[:, pl.ds(base, WIN)] += jax.lax.dot_general(
                byte.astype(jnp.float32).astype(jnp.bfloat16),
                onehot.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (8, WIN)
            return run + jnp.sum(l)

        bits = jax.lax.fori_loop(0, ROWS, row, jnp.int32(0), unroll=True)
        acc = acc_ref[...].astype(jnp.int32)
        hi_w = acc[0:1] | (acc[1:2] << 8) | (acc[2:3] << 16) | (acc[3:4] << 24)
        lo_w = acc[4:5] | (acc[5:6] << 8) | (acc[6:7] << 16) | (acc[7:8] << 24)
        # The last word's low part never exists (CAP_PAD > CAP_WORDS), so
        # the roll's wrap-around carries a zero into word 0.
        words_ref[pl.ds(g, 1), :] = hi_w | pltpu.roll(lo_w, 1, 1)
        bits_ref[pl.ds(g, 1), :] = jnp.full((1, LANES), bits, jnp.int32)
        return carry

    jax.lax.fori_loop(0, g_step, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lookup_pack_pallas(symbols: jnp.ndarray, codes: jnp.ndarray,
                       lengths: jnp.ndarray, *, interpret: bool | None = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """symbols (N,) through the book (codes, lengths) (≤ 256 entries of
    ≤ 16 bits) → (block words (NB, CAP_WORDS) uint32, block bits (NB,)).

    A symbol ``NO_SYMBOL`` carries no code (length 0), and N is padded to
    a BLOCK multiple with it: such slots contribute no bits.
    """
    n = symbols.shape[0]
    nb = max((n + BLOCK - 1) // BLOCK, 1)
    g = min(G, nb)          # fewer than G blocks: one full-array step
    nb_pad = -(-nb // g) * g
    s = jnp.pad(symbols.astype(jnp.int32), (0, nb_pad * BLOCK - n),
                constant_values=NO_SYMBOL).reshape(-1, LANES)

    vma = jax.typeof(s).vma     # varying like its input under shard_map
    words, bits = pl.pallas_call(
        functools.partial(_pack_kernel, g_step=g),
        grid=(nb_pad // g,),
        in_specs=[pl.BlockSpec((g * ROWS, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((LUT_ROWS, N_SYMBOLS), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((g, CAP_PAD), lambda i: (i, 0)),
                   pl.BlockSpec((g, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, CAP_PAD), jnp.int32, vma=vma),
                   jax.ShapeDtypeStruct((nb_pad, LANES), jnp.int32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((ROWS, LANES), jnp.int32),
                        pltpu.VMEM((ROWS, LANES), jnp.int32),
                        pltpu.VMEM((ROWS, LANES), jnp.int32),
                        pltpu.VMEM((8, CAP_PAD), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(s, lut_rows(codes, lengths))
    words = jax.lax.bitcast_convert_type(words[:nb, :CAP_WORDS], jnp.uint32)
    return words, bits[:nb, 0]
