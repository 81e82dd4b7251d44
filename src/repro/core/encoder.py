"""Single-stage Huffman encoder (the paper's contribution) plus the
three-stage baseline and a NumPy reference codec.

Single-stage = the critical path touches the data exactly once: each
symbol is mapped through a fixed (code, length) LUT and the codewords are
bit-packed.  No frequency scan, no tree build, no codebook on the wire.

The jit encoder works on fixed-size inputs and returns a worst-case-sized
word buffer plus the true bit count — variable-length output with static
shapes, which is what a fixed-function link encoder produces into its
transmit FIFO as well.  Bit order: MSB-first within big-endian 32-bit
words (network order), matching the canonical-decode table walk.

The decoder is a ``lax.scan`` over output symbols doing the canonical
first-code/offset walk — O(1) table state, fully jittable.  A pure-Python
codec (`encode_np`/`decode_np`) serves as the independent oracle for
property tests.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .codebook import Codebook, build_codebook
from .huffman import MAX_CODE_LEN

__all__ = [
    "encode_jit", "decode_jit", "encode_np", "decode_np",
    "three_stage_encode", "single_stage_encode",
    "encoded_size_bits", "packed_words_capacity", "EncodeResult",
    "ChunkedStream", "DEFAULT_CHUNK", "chunk_capacity_words",
    "chunk_counts_for", "concat_chunks",
    "encode_chunked_jit", "decode_chunks_jit", "recode_chunks_jit",
    "decode_chunks_multisym_jit", "multisym_table_args", "DECODE_BACKENDS",
    "encode_chunked", "decode_chunked", "decode_dispatch", "pack_path",
]

# Per-call symbol cap so bit offsets fit comfortably in uint32 cumsums.
_MAX_SYMBOLS = 1 << 26

# Default symbols per chunk for the streaming/chunked wire format — keep
# in sync with kernels.bitpack.BLOCK so kernel block streams interoperate.
DEFAULT_CHUNK = 2048


def chunk_capacity_words(chunk: int, max_len: int = MAX_CODE_LEN) -> int:
    """Worst-case uint32 words per chunk (+1 pad word for window reads).

    Ceiling division matters: with floor (as shipped before PR 3), odd
    chunk sizes made the "+1" word part of the worst-case payload
    instead of a true pad, so decoders clamping their two-word window
    fetch to ``cap - 2`` misread the final codewords of a
    near-incompressible chunk.  For ``chunk * max_len`` divisible by 32
    (every power-of-two chunk, incl. ``bitpack.BLOCK``) the value — and
    the wire format — is unchanged.

    Codec note: this capacity is the wire contract for *every* codec, so
    any book riding a chunked buffer must have its longest code ≤
    ``max_len``.  Huffman books enforce that by construction
    (package-merge is length-limited); QLC books validate it at build
    (``core.qlc.qlc_book_from_lengths`` rejects lengths > max_len),
    keeping buffer shapes codec-independent.
    """
    return (chunk * max_len + 31) // 32 + 1


def chunk_counts_for(n_symbols: int, chunk: int) -> np.ndarray:
    """Symbols per chunk for an n-symbol stream: all full except the tail."""
    nb = max((n_symbols + chunk - 1) // chunk, 1)
    counts = np.full(nb, chunk, dtype=np.int32)
    counts[-1] = n_symbols - (nb - 1) * chunk
    return counts


def concat_chunks(blocks: jnp.ndarray, chunk_counts: np.ndarray) -> jnp.ndarray:
    """(NB, chunk) padded symbol blocks → flat (Σcounts,) uint8.

    Only the tail chunk may be partial (the chunked-format invariant),
    so this is a reshape plus at most one tail slice.
    """
    counts = np.asarray(chunk_counts)
    if int(counts[-1]) == blocks.shape[1]:
        return blocks.reshape(-1).astype(jnp.uint8)
    head = blocks[:-1].reshape(-1)
    tail = blocks[-1, : int(counts[-1])]
    return jnp.concatenate([head, tail]).astype(jnp.uint8)


def packed_words_capacity(n_symbols: int, max_len: int = MAX_CODE_LEN) -> int:
    """Static worst-case uint32 word count (+1 pad word for window reads)."""
    return (n_symbols * max_len + 31) // 32 + 1


@dataclass
class EncodeResult:
    words: jnp.ndarray      # (capacity,) uint32 — MSB-first bitstream
    n_bits: jnp.ndarray     # () uint32 — true payload size
    n_symbols: int
    book_id: int = -1

    def payload_bytes(self) -> float:
        return float(self.n_bits) / 8.0


# --------------------------------------------------------------------------
# jit bit-packing encoder
# --------------------------------------------------------------------------
def pack_path(width: int, backend: str) -> str:
    """How ``_encode_rows`` looks up and packs rows of ``width`` symbols
    on ``backend``: ``"kernel"`` — the Mosaic lookup-and-pack kernel
    (``kernels.bitpack``) — where the rows are its BLOCK wide and the
    backend compiles it (a TPU); ``"xla"`` — a gather of the book and
    two scatter-adds — everywhere else.  Both give the same words, bit
    for bit."""
    from ..kernels.bitpack import BLOCK
    from ..kernels.platform import compiles_with_mosaic
    return ("kernel" if width == BLOCK and compiles_with_mosaic(backend)
            else "xla")


def _encode_rows(sym: jnp.ndarray, valid: jnp.ndarray, codes: jnp.ndarray,
                 lengths: jnp.ndarray, cap: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared encode core: per-row codebook lookup and MSB-first packing.

    sym: (NB, C) int32 symbols; valid: (NB, C) bool (False ⇒ the slot
    carries no symbol and contributes no bits).  Returns (words (NB, cap)
    uint32, bits (NB,) int32), through the path ``pack_path`` picks for
    the backend tracing the call.
    """
    from ..kernels.bitpack import CAP_WORDS
    if (cap <= CAP_WORDS
            and pack_path(sym.shape[1], jax.default_backend()) == "kernel"):
        return _encode_rows_kernel(sym, valid, codes, lengths, cap)
    return _pack_rows_xla(*_masked_rows(sym, valid, codes, lengths), cap)


def _encode_rows_kernel(sym: jnp.ndarray, valid: jnp.ndarray,
                        codes: jnp.ndarray, lengths: jnp.ndarray, cap: int,
                        *, interpret: bool | None = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``_encode_rows`` by the lookup-and-pack kernel: rows of
    ``bitpack.BLOCK`` symbols are its blocks, slots that carry none get
    ``NO_SYMBOL``, and ``cap`` ≤ its ``CAP_WORDS`` (codes ≤ 16 bits)
    words hold every row's bits."""
    from ..kernels.bitpack import NO_SYMBOL, lookup_pack_pallas
    words, bits = lookup_pack_pallas(
        jnp.where(valid, sym, NO_SYMBOL).reshape(-1), codes, lengths,
        interpret=interpret)
    return words[:, :cap], bits


def _masked_rows(sym, valid, codes, lengths):
    """The XLA path's lookup: (code, length) rows, 0 where not valid."""
    v = codes[sym].astype(jnp.uint32) * valid.astype(jnp.uint32)
    l = lengths[sym].astype(jnp.uint32) * valid.astype(jnp.uint32)
    return v, l


def _pack_rows_xla(v: jnp.ndarray, l: jnp.ndarray, cap: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packing by two masked shifts and two scatter-adds.

    v: (NB, C) uint32 right-aligned codewords; l: (NB, C) uint32 lengths
    (0 ⇒ the slot contributes no bits).

    A codeword of length ≤16 starting at bit offset o spans at most two
    32-bit words; high/low parts assemble via scatter-add — fields are
    disjoint so add ≡ or.
    """
    nb = v.shape[0]
    if v.shape[1] == 0:                              # empty stream
        return (jnp.zeros((nb, cap), jnp.uint32),
                jnp.zeros((nb,), jnp.int32))
    ends = jnp.cumsum(l, axis=1, dtype=jnp.uint32)
    offs = ends - l                                  # exclusive prefix sum
    bits = ends[:, -1].astype(jnp.int32)

    pos = offs & jnp.uint32(31)                      # bit position in word
    idx = (offs >> jnp.uint32(5)).astype(jnp.int32)  # word index in row

    # sh = 32 - pos - l : left-shift that right-aligns the code's end with
    # the word end.  Negative sh means the low |sh| bits spill to word+1.
    sh = 32 - pos.astype(jnp.int32) - l.astype(jnp.int32)
    hi = jnp.where(sh >= 0, v << jnp.clip(sh, 0, 31).astype(jnp.uint32),
                   v >> jnp.clip(-sh, 0, 31).astype(jnp.uint32))
    lo = jnp.where(sh < 0, v << jnp.clip(32 + sh, 0, 31).astype(jnp.uint32),
                   jnp.uint32(0))

    flat_idx = (jnp.arange(nb, dtype=jnp.int32)[:, None] * cap + idx).reshape(-1)
    words = jnp.zeros((nb * cap,), jnp.uint32)
    words = words.at[flat_idx].add(hi.reshape(-1), mode="drop")
    words = words.at[flat_idx + 1].add(lo.reshape(-1), mode="drop")
    return words.reshape(nb, cap), bits


@partial(jax.jit, static_argnames=("max_len",))
def encode_jit(symbols: jnp.ndarray, codes: jnp.ndarray, lengths: jnp.ndarray,
               max_len: int = MAX_CODE_LEN) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack ``symbols`` through the (codes, lengths) LUT into a bitstream.

    symbols: (N,) uint8/int32 — N static.
    codes:   (n_sym,) uint32 canonical codes (MSB-first, right-aligned)
    lengths: (n_sym,) int32 — all > 0 (total code)
    Returns (words, n_bits): (capacity,) uint32 and scalar uint32.
    """
    n = symbols.shape[0]
    if n > _MAX_SYMBOLS:
        raise ValueError(f"chunk too large: {n} > {_MAX_SYMBOLS}")
    sym = symbols.astype(jnp.int32)[None, :]
    words, bits = _encode_rows(sym, jnp.ones(sym.shape, bool), codes,
                               lengths, packed_words_capacity(n, max_len))
    return words.reshape(-1), bits[0].astype(jnp.uint32)


# --------------------------------------------------------------------------
# jit canonical decoder (lax.scan)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_symbols", "max_len"))
def decode_jit(words: jnp.ndarray, first_code: jnp.ndarray,
               base_index: jnp.ndarray, num_codes: jnp.ndarray,
               sorted_symbols: jnp.ndarray, n_symbols: int,
               max_len: int = MAX_CODE_LEN) -> jnp.ndarray:
    """Decode ``n_symbols`` symbols from an MSB-first canonical bitstream.

    Per step: read a max_len-bit window at the cursor, find the unique
    code length l with first_code[l] ≤ window>>(max_len-l) <
    first_code[l]+num_codes[l], emit sorted_symbols[base+offset], advance.
    The l-search is vectorized over the ≤16 candidate lengths.
    """
    fc = first_code.astype(jnp.int32)
    bi = base_index.astype(jnp.int32)
    nc = num_codes.astype(jnp.int32)
    ss = sorted_symbols.astype(jnp.int32)
    ls = jnp.arange(1, max_len + 1, dtype=jnp.int32)          # (L,)

    def step(bit_pos, _):
        widx = (bit_pos >> jnp.uint32(5)).astype(jnp.int32)
        pin = bit_pos & jnp.uint32(31)
        w0 = words[widx]
        w1 = words[widx + 1]
        hi = w0 << pin
        lo = jnp.where(pin == 0, jnp.uint32(0),
                       w1 >> jnp.clip(32 - pin.astype(jnp.int32), 0, 31
                                      ).astype(jnp.uint32))
        window = ((hi | lo) >> jnp.uint32(32 - max_len)).astype(jnp.int32)
        cand = window >> (max_len - ls)                        # (L,)
        off = cand - fc[ls]
        valid = (off >= 0) & (off < nc[ls])
        li = jnp.argmax(valid)                                 # smallest valid l
        l = ls[li]
        sym = ss[jnp.clip(bi[l] + off[li], 0, ss.shape[0] - 1)]
        return bit_pos + l.astype(jnp.uint32), sym

    # Initial cursor derives from `words` (0-valued) so its varying-axes
    # type matches the body output under shard_map (see shard-map vma docs).
    cursor0 = words[0] & jnp.uint32(0)
    _, syms = jax.lax.scan(step, cursor0, None, length=n_symbols)
    return syms.astype(jnp.uint8)


def decode_with_book(words: jnp.ndarray, book: Codebook,
                     n_symbols: int) -> jnp.ndarray:
    from .codec import codec_for_book
    return codec_for_book(book).decode_plane(words, book, n_symbols)


# --------------------------------------------------------------------------
# Chunked streaming format: fixed-symbol chunks, each independently packed
# and word-aligned, with a per-chunk bit-count header.  Chunks are
# independent decode entry points, which is what lets (a) the Pallas
# decoder parallelize over its grid and (b) streaming collectives overlap
# chunk N's decode with chunk N+1's transfer.
# --------------------------------------------------------------------------
@dataclass
class ChunkedStream:
    """A Huffman bitstream cut into independently-decodable chunks.

    block_words[b] holds chunk b's MSB-first packed words (word-aligned
    start, slack zeroed); block_bits[b] is its true payload size — the
    per-chunk header a streaming receiver reads before the chunk body.
    """
    block_words: jnp.ndarray   # (NB, cap) uint32
    block_bits: jnp.ndarray    # (NB,) int32
    n_symbols: int
    chunk: int
    book_id: int = -1

    @property
    def n_chunks(self) -> int:
        return self.block_words.shape[0]

    def chunk_counts(self) -> np.ndarray:
        """Symbols per chunk (static: derived from n_symbols, chunk)."""
        return chunk_counts_for(self.n_symbols, self.chunk)

    def payload_bits(self) -> int:
        return int(jnp.sum(self.block_bits))

    def header_bits(self) -> int:
        """Per-chunk bit-count headers (32-bit each) the wire carries."""
        return 32 * self.n_chunks


@partial(jax.jit, static_argnames=("chunk", "max_len"))
def encode_chunked_jit(symbols: jnp.ndarray, codes: jnp.ndarray,
                       lengths: jnp.ndarray, chunk: int = DEFAULT_CHUNK,
                       max_len: int = MAX_CODE_LEN
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack ``symbols`` into per-chunk word-aligned bitstreams.

    Same bitfield math as ``encode_jit`` applied per chunk row: pad
    positions get length 0 (and code 0) so they contribute no bits.
    Safe under jit/shard_map; at chunk == bitpack.BLOCK on a TPU the
    ``lookup_pack_pallas`` kernel looks the rows up and packs them
    (``pack_path``), with the same words.

    Returns (block_words (NB, cap) uint32, block_bits (NB,) int32).
    """
    return _encode_rows(*_chunk_rows(symbols, chunk), codes, lengths,
                        chunk_capacity_words(chunk, max_len))


def _chunk_rows(symbols, chunk: int):
    """A flat stream's (NB, chunk) symbol rows and their valid mask: the
    pad slots of the tail chunk carry no symbol."""
    n = symbols.shape[0]
    if n > _MAX_SYMBOLS:
        raise ValueError(f"chunk too large: {n} > {_MAX_SYMBOLS}")
    nb = max((n + chunk - 1) // chunk, 1)
    pad = nb * chunk - n
    sym = jnp.pad(symbols.astype(jnp.int32), (0, pad)).reshape(nb, chunk)
    valid = (jnp.arange(chunk, dtype=jnp.int32)[None, :]
             + jnp.arange(nb, dtype=jnp.int32)[:, None] * chunk) < n
    return sym, valid


@partial(jax.jit, static_argnames=("max_len",))
def recode_chunks_jit(sym_blocks: jnp.ndarray, chunk_counts: jnp.ndarray,
                      codes: jnp.ndarray, lengths: jnp.ndarray,
                      max_len: int = MAX_CODE_LEN
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Re-encode already-blocked symbols — the per-hop recode fast path.

    A ring hop decodes an incoming chunk straight into its (NB, chunk)
    block layout, reduces, and must re-encode before forwarding.  This
    skips ``encode_chunked_jit``'s flatten/pad/reshape (the blocks are
    already chunk-aligned) and takes per-chunk symbol counts directly,
    so no tables or chunk geometry are re-derived.  Bit-identical to
    ``encode_chunked_jit`` on the equivalent flat stream.

    sym_blocks: (NB, chunk) uint8/int32; chunk_counts: (NB,) int32.
    Returns (block_words (NB, cap) uint32, block_bits (NB,) int32).
    """
    return _encode_rows(*_block_rows(sym_blocks, chunk_counts), codes,
                        lengths,
                        chunk_capacity_words(sym_blocks.shape[1], max_len))


def _block_rows(sym_blocks, chunk_counts):
    """Blocked symbols as int32 rows and their valid mask: slots past
    each chunk's count carry no symbol."""
    chunk = sym_blocks.shape[1]
    valid = (jnp.arange(chunk, dtype=jnp.int32)[None, :]
             < chunk_counts.astype(jnp.int32)[:, None])
    return sym_blocks.astype(jnp.int32), valid


@partial(jax.jit, static_argnames=("chunk", "max_len"))
def decode_chunks_jit(block_words: jnp.ndarray, chunk_counts: jnp.ndarray,
                      first_code: jnp.ndarray, base_index: jnp.ndarray,
                      num_codes: jnp.ndarray, sorted_symbols: jnp.ndarray,
                      chunk: int = DEFAULT_CHUNK,
                      max_len: int = MAX_CODE_LEN) -> jnp.ndarray:
    """Scan-based chunked decode: vmap of the canonical walk over chunks.

    The ``scan`` backend, and the semantics oracle of the Pallas decode
    kernel.  block_words (NB, cap) uint32, chunk_counts (NB,) int32 →
    (NB, chunk) int32 symbols, zero-filled past each chunk's count.
    """
    fc = first_code.astype(jnp.int32)
    bi = base_index.astype(jnp.int32)
    nc = num_codes.astype(jnp.int32)
    ss = sorted_symbols.astype(jnp.int32)
    ls = jnp.arange(1, max_len + 1, dtype=jnp.int32)
    cap = block_words.shape[1]

    def one_chunk(words, count):
        def step(bit_pos, k):
            widx = jnp.minimum((bit_pos >> jnp.uint32(5)).astype(jnp.int32),
                               cap - 2)
            pin = bit_pos & jnp.uint32(31)
            w0 = words[widx]
            w1 = words[widx + 1]
            hi = w0 << pin
            lo = jnp.where(pin == 0, jnp.uint32(0),
                           w1 >> jnp.clip(32 - pin.astype(jnp.int32), 0, 31
                                          ).astype(jnp.uint32))
            window = ((hi | lo) >> jnp.uint32(32 - max_len)).astype(jnp.int32)
            cand = window >> (max_len - ls)
            off = cand - fc[ls]
            valid = (off >= 0) & (off < nc[ls])
            li = jnp.argmax(valid)
            l = ls[li]
            sym = ss[jnp.clip(bi[l] + off[li], 0, ss.shape[0] - 1)]
            live = k < count
            adv = jnp.where(live, l, 0).astype(jnp.uint32)
            return bit_pos + adv, jnp.where(live, sym, 0)

        cursor0 = words[0] & jnp.uint32(0)
        _, syms = jax.lax.scan(step, cursor0,
                               jnp.arange(chunk, dtype=jnp.int32))
        return syms

    return jax.vmap(one_chunk)(block_words.astype(jnp.uint32),
                               chunk_counts.astype(jnp.int32))


# --------------------------------------------------------------------------
# Multi-symbol table-driven decode (the K-bit window LUT).  One gather
# per window emits up to s_max symbols, so the per-symbol canonical walk
# is amortized; windows whose first code is longer than K bits fall back
# to the canonical subtraction over the remaining lengths K+1..max_len.
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("chunk", "max_len"))
def decode_chunks_multisym_jit(block_words: jnp.ndarray,
                               chunk_counts: jnp.ndarray,
                               step_tab: jnp.ndarray,
                               emit_tab: jnp.ndarray,
                               chunk: int = DEFAULT_CHUNK,
                               max_len: int = MAX_CODE_LEN) -> jnp.ndarray:
    """Chunked multi-symbol decode: window-replay scan + gather emission.

    Phase 1 — the only sequential part: a ``lax.scan`` over output
    slots (all chunks advance in lockstep).  A window's decode work
    happens *once*, when the previous window is exhausted: one gather
    from the precomputed half-word window array and one from
    ``MultiSymTables.step_tab``, whose entry packs the window's
    absolute emit-table pointer, symbol count and total bit advance
    (slow windows — first code longer than K bits — carry count 1 and
    their true code length).  The following count−1 steps just replay:
    ``ptr + 1``.  So the canonical walk, the cursor split and the
    bit-position bookkeeping are amortized across the window's symbols,
    and the body is two gathers plus a few selects — against the
    per-symbol walk's two word fetches, 16-way subtraction, argmax and
    symbol gather every step.  Each step's ``ptr`` goes into the scan
    outputs, which XLA writes at the static step index.  (Formulations
    that scatter decoded symbols at data-dependent positions inside the
    loop copy their output buffer every iteration and benchmark ~10×
    slower end to end; inverting a per-window trajectory afterwards
    costs more binary-search gathers per symbol than it saves.)

    Phase 2 — fully parallel: every output slot is exactly one
    ``emit_tab[ptr]`` gather (the table concatenates the K-bit LUT rows
    with the full-window first-symbol table, so slow windows are just
    indices past ``2^k · s_max``).  Gathers only; no scatter anywhere.

    block_words (NB, cap) uint32, chunk_counts (NB,) int32,
    step_tab (2^max_len,) int32, emit_tab (2^k·s_max + 2^max_len,)
    int32 → (NB, chunk) int32 symbols, zero-filled past each chunk's
    count.  Bit-exact vs ``decode_chunks_jit`` / ``decode_np``.
    """
    from .huffman import STEP_CNT_BITS, STEP_PTR_BITS
    nb, cap = block_words.shape
    if step_tab.shape[0] != (1 << max_len):
        raise ValueError(f"step_tab has {step_tab.shape[0]} entries, "
                         f"expected 2^{max_len}")
    words = block_words.astype(jnp.uint32)
    counts = chunk_counts.astype(jnp.int32)
    stab = step_tab.astype(jnp.int32)
    etab = emit_tab.astype(jnp.int32)
    ptr_mask = (1 << STEP_PTR_BITS) - 1
    cnt_mask = (1 << STEP_CNT_BITS) - 1

    # Half-word window array: H[:, q] holds stream bits [16q, 16q+32), so
    # any 16-bit window is one gather plus two shifts in the scan body.
    nxt = jnp.concatenate([words[:, 1:], jnp.zeros((nb, 1), jnp.uint32)],
                          axis=1)
    H = jnp.stack([words, (words << 16) | (nxt >> 16)],
                  axis=2).reshape(nb, 2 * cap)

    def body(carry, _):
        bit_pos, rem, ptr = carry
        fresh = rem == 0                       # current window exhausted?
        q = jnp.minimum((bit_pos >> jnp.uint32(4)).astype(jnp.int32),
                        2 * cap - 1)
        h = jnp.take_along_axis(H, q[:, None], axis=1)[:, 0]
        win = ((h << (bit_pos & jnp.uint32(15)))
               >> jnp.uint32(32 - max_len)).astype(jnp.int32)
        e = stab[win]
        adv = jnp.where(fresh, (e >> (STEP_PTR_BITS + STEP_CNT_BITS)), 0)
        ptr = jnp.where(fresh, e & ptr_mask, ptr + 1)
        rem = jnp.where(fresh, (e >> STEP_PTR_BITS) & cnt_mask, rem) - 1
        return (bit_pos + adv.astype(jnp.uint32), rem, ptr), ptr

    # Carries derive from `words` (0-valued) so their varying-axes types
    # match the body output under shard_map (same trick as decode_jit).
    zero = (words[0, 0] & jnp.uint32(0)).astype(jnp.int32)
    zeros_nb = jnp.zeros((nb,), jnp.int32) + zero
    # unroll=8 amortizes XLA:CPU per-iteration loop overhead (~2× end to
    # end here); measured best among {1, 2, 4, 8, 16}.
    (_, _, _), ptrs = jax.lax.scan(
        body, (zeros_nb.astype(jnp.uint32), zeros_nb, zeros_nb),
        None, length=chunk, unroll=min(8, chunk))

    # ---- phase 2: one gather per output slot.  ptrs (chunk, NB).
    out = etab[ptrs.T]
    o = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    return jnp.where(o < counts[:, None], out, 0)


DECODE_BACKENDS = ("auto", "pallas", "scan", "multisym", "multisym_pallas")


def multisym_table_args(book: Codebook, *, full: bool = True):
    """Device arrays for a book's multisym LUT.

    ``full=True`` → (step_tab, emit_tab): the folded 2^max_len tables
    the XLA window-replay scan consumes.  ``full=False`` → (syms, meta):
    the compact 2^K pair the Pallas kernel keeps in VMEM next to its
    inline slow path.
    """
    mt = book.multisym_tables()
    if full:
        return jnp.asarray(mt.step_tab), jnp.asarray(mt.emit_tab)
    return jnp.asarray(mt.syms), jnp.asarray(mt.meta)


def encode_chunked(symbols: jnp.ndarray, book: Codebook, *,
                   chunk: int = DEFAULT_CHUNK) -> ChunkedStream:
    """Single-stage encode into the chunked streaming wire format."""
    sym = jnp.asarray(symbols, dtype=jnp.uint8).reshape(-1)
    words, bits = encode_chunked_jit(sym, jnp.asarray(book.codes),
                                     jnp.asarray(book.lengths), chunk=chunk,
                                     max_len=book.max_len)
    return ChunkedStream(block_words=words, block_bits=bits,
                         n_symbols=int(sym.shape[0]), chunk=chunk,
                         book_id=book.book_id)


def decode_chunked(stream: ChunkedStream, book, *,
                   backend: str = "auto") -> jnp.ndarray:
    """Decode a ChunkedStream back to its uint8 symbols.

    The book's codec (``core.codec``, tagged on the book itself) picks
    the decoder family; ``backend`` selects within it — for huffman:
    "pallas" (per-symbol canonical-walk kernel), "scan" (XLA lax.scan),
    "multisym" (K-bit window LUT), "multisym_pallas"; for qlc: "scan" /
    "pallas".  "auto" here means **pallas** for either codec (Pallas
    interpret mode on the CPU, Mosaic on a TPU) — this entry point's
    historical contract —
    unlike spec-level "auto", which resolves to the codec's fastest
    portable default.
    """
    from .codec import codec_for_book
    counts = jnp.asarray(stream.chunk_counts())
    out = codec_for_book(book).decode_blocks(
        stream.block_words, counts, book, stream.chunk,
        "pallas" if backend == "auto" else backend)
    return concat_chunks(out, stream.chunk_counts())


def decode_dispatch(stream, book: Codebook, n_symbols: int = None, *,
                    backend: str = "auto") -> jnp.ndarray:
    """Route a stream to the right decoder.

    ChunkedStream → chunked device decode (the named backend);
    monolithic word buffer → the canonical ``decode_jit`` scan walk
    (a monolithic stream has no chunk entry points to parallelize over).
    """
    if isinstance(stream, ChunkedStream):
        return decode_chunked(stream, book, backend=backend)
    if n_symbols is None:
        raise ValueError("monolithic decode needs n_symbols")
    return decode_with_book(stream, book, n_symbols)


# --------------------------------------------------------------------------
# NumPy reference codec (independent oracle for property tests)
# --------------------------------------------------------------------------
def encode_np(symbols: np.ndarray, codes: np.ndarray,
              lengths: np.ndarray) -> Tuple[np.ndarray, int]:
    """Bit-exact reference encoder: plain Python bit twiddling."""
    bits = []
    for s in np.asarray(symbols).astype(np.int64):
        l = int(lengths[s])
        c = int(codes[s])
        bits.extend(((c >> (l - 1 - i)) & 1) for i in range(l))
    n_bits = len(bits)
    n_words = (n_bits + 31) // 32 + 1
    words = np.zeros(n_words, dtype=np.uint32)
    for i, b in enumerate(bits):
        if b:
            words[i >> 5] |= np.uint32(1) << np.uint32(31 - (i & 31))
    return words, n_bits


def decode_np(words: np.ndarray, n_symbols: int, book: Codebook) -> np.ndarray:
    t = book.tables
    out = np.zeros(n_symbols, dtype=np.uint8)
    pos = 0
    for k in range(n_symbols):
        code = 0
        l = 0
        while True:
            l += 1
            bit = (int(words[pos >> 5]) >> (31 - (pos & 31))) & 1
            pos += 1
            code = (code << 1) | bit
            off = code - int(t.first_code[l])
            if 0 <= off < int(t.num_codes[l]):
                out[k] = t.sorted_symbols[int(t.base_index[l]) + off]
                break
            if l >= t.max_len:
                raise ValueError("corrupt stream")
    return out


# --------------------------------------------------------------------------
# The two encoder designs the paper compares
# --------------------------------------------------------------------------
def three_stage_encode(symbols: np.ndarray, *, n_alphabet: int = 256,
                       max_len: int = MAX_CODE_LEN):
    """Baseline: scan → build codebook → encode.  Returns
    (EncodeResult, Codebook, stage_seconds dict).  The codebook must ride
    with the message (lengths vector, n_alphabet bytes) — accounted in
    ``wire_bits``."""
    t0 = time.perf_counter()
    counts = np.bincount(np.asarray(symbols).reshape(-1), minlength=n_alphabet)
    t1 = time.perf_counter()
    book = build_codebook(counts, max_len=max_len)
    t2 = time.perf_counter()
    words, n_bits = encode_jit(jnp.asarray(symbols, dtype=jnp.uint8),
                               jnp.asarray(book.codes),
                               jnp.asarray(book.lengths), max_len=max_len)
    jax.block_until_ready(words)
    t3 = time.perf_counter()
    res = EncodeResult(words=words, n_bits=n_bits, n_symbols=len(symbols))
    stages = {"freq_scan_s": t1 - t0, "tree_build_s": t2 - t1,
              "encode_s": t3 - t2,
              "wire_bits": int(n_bits) + 8 * n_alphabet}  # + codebook payload
    return res, book, stages


def single_stage_encode(symbols: jnp.ndarray, book: Codebook) -> EncodeResult:
    """The paper's encoder: one pass through a fixed codebook.  Wire
    payload = header (book id + count) + bits; no codebook, no scan."""
    words, n_bits = encode_jit(jnp.asarray(symbols, dtype=jnp.uint8),
                               jnp.asarray(book.codes),
                               jnp.asarray(book.lengths),
                               max_len=book.max_len)
    return EncodeResult(words=words, n_bits=n_bits, n_symbols=int(symbols.shape[0]),
                        book_id=book.book_id)


def encoded_size_bits(counts, lengths) -> jnp.ndarray:
    """Ledger-mode exact size: histogram · lengths (device-friendly dot)."""
    return jnp.dot(jnp.asarray(counts, jnp.float32),
                   jnp.asarray(lengths, jnp.float32))
