"""jit'd public wrappers over the Pallas kernels.

Every kernel compiles through Mosaic on a TPU and runs in Pallas
interpret mode only when the backend running the call is the CPU
(``kernels.platform.resolve_interpret``).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..core.codebook import Codebook
from .encode import encode_lookup_pallas
from .histogram import histogram256_pallas


def histogram256(symbols: jnp.ndarray) -> jnp.ndarray:
    """256-bin histogram of a uint8 symbol stream (Pallas on TPU)."""
    return histogram256_pallas(symbols)


def encode_lookup(symbols: jnp.ndarray, lut: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-stage codebook lookup: (codes, lengths, total_bits)."""
    return encode_lookup_pallas(symbols, lut)


def encode_with_book(symbols: jnp.ndarray, book: Codebook):
    """Full single-stage encode using the Pallas LUT pass + jnp bit-pack.

    Returns an EncodeResult (same contract as core.encoder).  The packing
    prefix-sum consumes the kernel's (code, length) pairs; on real
    hardware that stage lives in the link encoder (see DESIGN.md §3).
    """
    from ..core.encoder import EncodeResult, packed_words_capacity
    import jax

    codes, lens, _ = encode_lookup(symbols, jnp.asarray(book.code_lut()))
    n = int(symbols.size)

    # Bit-pack (same scheme as core.encoder.encode_jit, reusing its math).
    l = lens.astype(jnp.uint32)
    v = codes.astype(jnp.uint32)
    ends = jnp.cumsum(l, dtype=jnp.uint32)
    offs = ends - l
    n_bits = ends[-1]
    pos = offs & jnp.uint32(31)
    idx = (offs >> jnp.uint32(5)).astype(jnp.int32)
    sh = 32 - pos.astype(jnp.int32) - l.astype(jnp.int32)
    hi = jnp.where(sh >= 0, v << jnp.clip(sh, 0, 31).astype(jnp.uint32),
                   v >> jnp.clip(-sh, 0, 31).astype(jnp.uint32))
    lo = jnp.where(sh < 0, v << jnp.clip(32 + sh, 0, 31).astype(jnp.uint32),
                   jnp.uint32(0))
    words = jnp.zeros((packed_words_capacity(n, book.max_len),), jnp.uint32)
    words = words.at[idx].add(hi, mode="drop").at[idx + 1].add(lo, mode="drop")
    return EncodeResult(words=words, n_bits=n_bits, n_symbols=n,
                        book_id=book.book_id)


def message_bits(symbols: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Ledger probe: exact encoded size via kernel histogram · lengths."""
    hist = histogram256(symbols).astype(jnp.float32)
    return jnp.dot(hist, jnp.asarray(lengths, jnp.float32))


def merge_block_streams(block_words, block_bits) -> "tuple":
    """Stitch per-block bitstreams (from lookup_pack_pallas) into one
    contiguous MSB-first stream.  One barrel shift per block — the
    transmit-FIFO side of the split (host/jnp; O(total words))."""
    import numpy as np

    bw = np.asarray(block_words)
    bb = np.asarray(block_bits, dtype=np.int64)
    total_bits = int(bb.sum())
    out = np.zeros(total_bits // 32 + 2, dtype=np.uint32)
    off = 0
    for words, nbits in zip(bw, bb):
        nbits = int(nbits)
        if nbits == 0:
            continue
        nw = (nbits + 31) // 32 + 1
        w = words[:nw].astype(np.uint64)
        s = off & 31
        base = off >> 5
        if s == 0:
            contrib = w
        else:
            contrib = (w >> s) | (np.concatenate(
                [np.zeros(1, np.uint64), w[:-1]]) << (32 - s)) & 0xFFFFFFFF
            contrib &= 0xFFFFFFFF
            tail = (w[-1] << (32 - s)) & 0xFFFFFFFF
            contrib = np.concatenate([contrib, tail[None]])
        end = min(base + len(contrib), len(out))
        out[base:end] |= contrib[: end - base].astype(np.uint32)
        off += nbits
    return out, off


def pack_with_book(symbols, book):
    """Full kernel-path encode: lookup-and-pack kernel → merge.
    Bit-exact with core.encoder.encode_jit (tested)."""
    from .bitpack import lookup_pack_pallas

    words, bits = lookup_pack_pallas(symbols.reshape(-1),
                                     jnp.asarray(book.codes),
                                     jnp.asarray(book.lengths))
    return merge_block_streams(words, bits)


def decode_chunks(block_words, chunk_counts, book: Codebook, *,
                  chunk: int = 2048):
    """Chunked canonical decode via the Pallas kernel.

    block_words (NB, cap) uint32, chunk_counts (NB,) int32 → (NB, chunk)
    int32 symbols, zero past each count.  Inverse of lookup_pack_pallas /
    encode_chunked_jit chunk streams under the same codebook.
    """
    from .decode import decode_chunks_pallas

    t = book.tables
    return decode_chunks_pallas(
        jnp.asarray(block_words), jnp.asarray(chunk_counts),
        jnp.asarray(t.first_code), jnp.asarray(t.base_index),
        jnp.asarray(t.num_codes), jnp.asarray(t.sorted_symbols),
        chunk=chunk, max_len=t.max_len)


def decode_chunks_multisym(block_words, chunk_counts, book: Codebook, *,
                           chunk: int = 2048):
    """Chunked multi-symbol decode via the window-LUT Pallas kernel.

    Same contract as ``decode_chunks``; the K-bit tables come from the
    book's cached ``multisym_tables()``.
    """
    from .decode import decode_chunks_multisym_pallas

    t = book.tables
    mt = book.multisym_tables()
    return decode_chunks_multisym_pallas(
        jnp.asarray(block_words), jnp.asarray(chunk_counts),
        jnp.asarray(mt.syms), jnp.asarray(mt.meta),
        jnp.asarray(t.first_code), jnp.asarray(t.base_index),
        jnp.asarray(t.num_codes), jnp.asarray(t.sorted_symbols),
        chunk=chunk, max_len=t.max_len)


def decode_matmul(x, lo_words, hi_words, chunk_counts, books, *,
                  chunk: int, n_cols: int, interpret: bool | None = None):
    """Fused coded-weight matmul: x @ W from W's coded byte planes.

    lo/hi_words are (NB, cap) chunked coded streams of W (K, N)
    flattened row-major; books = {"lo": book, "hi": book} (both planes
    must share one codec).  Dispatches to the canonical-Huffman or QLC
    fused kernel on the books' ``codec_name``.  Returns (M, n_cols)
    float32, bit-exact vs ``ref.decode_matmul_ref``.
    """
    from .decode_matmul import decode_matmul_pallas, decode_matmul_qlc_pallas

    lo_b, hi_b = books["lo"], books["hi"]
    name = getattr(lo_b, "codec_name", "huffman")
    if getattr(hi_b, "codec_name", "huffman") != name:
        raise ValueError("decode_matmul: lo/hi books use different codecs")
    if name == "qlc":
        from ..core.qlc import qlc_kernel_args
        lo_lp, lo_bp, lo_st = qlc_kernel_args(lo_b)
        hi_lp, hi_bp, hi_st = qlc_kernel_args(hi_b)
        return decode_matmul_qlc_pallas(
            jnp.asarray(x), jnp.asarray(lo_words), jnp.asarray(hi_words),
            jnp.asarray(chunk_counts),
            jnp.stack([lo_lp, hi_lp]), jnp.stack([lo_bp, hi_bp]),
            jnp.stack([lo_st, hi_st]),
            chunk=chunk, n_cols=n_cols, interpret=interpret)
    lt, ht = lo_b.tables, hi_b.tables
    if lt.max_len != ht.max_len:
        raise ValueError("decode_matmul: lo/hi books disagree on max_len")
    ns = max(lt.sorted_symbols.shape[0], ht.sorted_symbols.shape[0])

    def _pad(sym):
        out = np.zeros((ns,), np.int32)
        out[:sym.shape[0]] = np.asarray(sym, np.int32)
        return out

    return decode_matmul_pallas(
        jnp.asarray(x), jnp.asarray(lo_words), jnp.asarray(hi_words),
        jnp.asarray(chunk_counts),
        jnp.stack([jnp.asarray(lt.first_code), jnp.asarray(ht.first_code)]),
        jnp.stack([jnp.asarray(lt.base_index), jnp.asarray(ht.base_index)]),
        jnp.stack([jnp.asarray(lt.num_codes), jnp.asarray(ht.num_codes)]),
        jnp.stack([jnp.asarray(_pad(lt.sorted_symbols)),
                   jnp.asarray(_pad(ht.sorted_symbols))]),
        chunk=chunk, n_cols=n_cols, max_len=lt.max_len, interpret=interpret)


def decode_with_book_kernel(symbols_stream, book: Codebook, n_symbols: int, *,
                            chunk: int = 2048):
    """Decode a kernel-path chunked stream back to (n_symbols,) uint8.

    symbols_stream is the (block_words, block_bits) pair produced by
    lookup_pack_pallas (block_bits is unused for decoding — the walk is
    symbol-counted — but belongs to the wire format as the per-chunk
    header).
    """
    from ..core.encoder import chunk_counts_for, concat_chunks

    block_words, _block_bits = symbols_stream
    counts = chunk_counts_for(n_symbols, chunk)
    out = decode_chunks(block_words, counts, book, chunk=chunk)
    return concat_chunks(out, counts)
