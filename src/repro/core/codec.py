"""Pluggable codec registry: histogram → book → encode/decode strategies.

A ``Codec`` is one entropy-coding strategy for the 256-symbol planes the
schemes produce: how probe histograms become a *book* (the per-plane
code table), how a book is reconstructed from its wire-portable lengths
vector, and which decode backends can consume its bitstreams.  The
registry mirrors ``comm.transport.TRANSPORTS`` — ``CompressionSpec``
names the codec as a static field and every layer (transport block
decode, ring hop codec, lifecycle rebuilds, serve decode-verify)
dispatches through ``CODECS`` instead of hard-coding Huffman.

Built-ins:

  huffman — the paper's single-stage canonical Huffman code
      (``core.huffman`` / ``core.codebook``): package-merge
      length-limited lengths, canonical codes, decode via the
      per-symbol canonical walk (``scan`` / ``pallas``) or the
      multi-symbol window LUT (``multisym`` / ``multisym_pallas``).
  qlc     — Quad Length Codes (``core.qlc``): exactly four code
      lengths, class named by the 2 leading bits, branchless table-free
      decode (``scan`` / ``pallas``).  Trades ≤ ~6% ratio on e4m3
      traffic for a large symbols/sec win on the ring hop path.

Both codecs share the wire format end-to-end: books expose
``codes`` / ``lengths`` / ``max_len`` so the single ``_encode_rows``
encode core packs either, and every book's ``max_len`` is bounded by
``MAX_CODE_LEN`` so ``chunk_capacity_words`` is codec-independent —
a spec can switch codecs without touching buffer shapes.

The module-level *default codec* is what ``codec="auto"`` specs and
``codec=None`` registry builds resolve to; the test suite's
``REPRO_TEST_CODEC`` fixture retargets it so the whole suite runs
under either codec (docs/codecs.md).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .huffman import MAX_CODE_LEN

__all__ = ["Codec", "HuffmanCodec", "QLCCodec", "CODECS", "register_codec",
           "get_codec", "codec_for_book", "default_codec",
           "set_default_codec"]


class Codec:
    """One entropy-coding strategy (book build + decode dispatch).

    Subclasses set ``name``, the supported ``backends`` tuple and the
    ``default_backend`` that ``"auto"`` resolves to, and implement
    ``build_book`` / ``book_from_lengths`` / ``decode_blocks``.  The
    books a codec produces must duck-type the encode surface
    (``codes`` / ``lengths`` / ``max_len`` / ``book_id`` / ``key`` /
    ``expected_bits_per_symbol``) and carry ``codec_name`` so
    ``codec_for_book`` can round-trip the dispatch.
    """

    name: str = "?"
    backends: Tuple[str, ...] = ()
    default_backend: str = "?"

    def resolve_backend(self, backend: str) -> str:
        """Map ``"auto"`` to this codec's default; validate the rest."""
        if backend == "auto":
            return self.default_backend
        if backend not in self.backends:
            raise ValueError(
                f"decode backend {backend!r} not supported by codec "
                f"{self.name!r}; one of {('auto',) + self.backends}")
        return backend

    def build_book(self, counts, *, book_id: int = -1,
                   key: Tuple[str, str, str] = ("", "", ""),
                   max_len: int = MAX_CODE_LEN, floor: int = 1,
                   n_symbols: Optional[int] = None):
        """Probe histogram → book (the codec's length-assignment rule)."""
        raise NotImplementedError

    def book_from_lengths(self, lengths, *, book_id: int = -1,
                          key: Tuple[str, str, str] = ("", "", ""),
                          max_len: int = MAX_CODE_LEN):
        """Reconstruct a book from its canonical lengths vector — what a
        receiver holds after the spec's ``plane_lengths`` ride the wire."""
        raise NotImplementedError

    def decode_blocks(self, words, counts, book, chunk: int, backend: str):
        """(NB, cap) words + (NB,) counts → (NB, chunk) symbol blocks."""
        raise NotImplementedError

    def decode_plane(self, words, book, n_symbols: int):
        """Monolithic decode: one whole-plane stream → (n_symbols,).

        Generic fallback: a monolithic stream of n symbols is exactly a
        single chunk of size n (``packed_words_capacity(n) ==
        chunk_capacity_words(n)``), so one ``decode_blocks`` row covers
        it.  Codecs with a dedicated monolithic walk override this.
        """
        counts = jnp.full((1,), n_symbols, jnp.int32)
        out = self.decode_blocks(words.reshape(1, -1), counts, book,
                                 n_symbols, self.default_backend)
        return out.reshape(-1)


CODECS: Dict[str, Codec] = {}


def register_codec(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    CODECS[cls.name] = cls()
    return cls


def get_codec(name: str) -> Codec:
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(f"unknown codec {name!r}; "
                         f"registered: {sorted(CODECS)}") from None


def codec_for_book(book) -> Codec:
    """The codec that produced ``book`` (via its ``codec_name`` tag)."""
    return get_codec(getattr(book, "codec_name", "huffman"))


_DEFAULT_CODEC = "huffman"


def default_codec() -> str:
    """The codec name that ``"auto"`` / ``None`` selections resolve to."""
    return _DEFAULT_CODEC


def set_default_codec(name: str) -> str:
    """Retarget the process-wide default codec; returns the previous one.

    This is how the test suite's ``REPRO_TEST_CODEC`` fixture runs the
    whole suite under either codec without touching every spec
    construction — production code selects explicitly via
    ``CompressionSpec.codec``.
    """
    global _DEFAULT_CODEC
    get_codec(name)                      # validate before swapping
    prev = _DEFAULT_CODEC
    _DEFAULT_CODEC = name
    return prev


@register_codec
class HuffmanCodec(Codec):
    """The paper's canonical Huffman code as a registered codec.

    Length assignment is package-merge (optimal under the max_len
    limit); decode dispatches across the four existing backends.  The
    ``multisym`` window-LUT walk is the default — fastest portable
    backend (docs/kernels.md).
    """

    name = "huffman"
    backends = ("multisym", "scan", "pallas", "multisym_pallas")
    default_backend = "multisym"

    def build_book(self, counts, *, book_id=-1, key=("", "", ""),
                   max_len=MAX_CODE_LEN, floor=1, n_symbols=None):
        from .codebook import build_codebook
        return build_codebook(counts, book_id=book_id, key=key,
                              max_len=max_len, floor=floor,
                              n_symbols=n_symbols, codec="huffman")

    def book_from_lengths(self, lengths, *, book_id=-1, key=("", "", ""),
                          max_len=MAX_CODE_LEN):
        from .codebook import Codebook
        from .huffman import canonical_codes, canonical_decode_tables
        lv = np.asarray(lengths, dtype=np.int32)
        return Codebook(book_id=book_id, key=tuple(key), lengths=lv,
                        codes=canonical_codes(lv),
                        tables=canonical_decode_tables(lv),
                        source_counts=np.zeros(lv.shape[0], np.int64),
                        max_len=max_len)

    def decode_blocks(self, words, counts, book, chunk, backend):
        from .encoder import (decode_chunks_jit, decode_chunks_multisym_jit,
                              multisym_table_args)
        backend = self.resolve_backend(backend)
        t = book.tables
        targs = (jnp.asarray(t.first_code), jnp.asarray(t.base_index),
                 jnp.asarray(t.num_codes), jnp.asarray(t.sorted_symbols))
        if backend == "pallas":
            from ..kernels.decode import decode_chunks_pallas
            return decode_chunks_pallas(words, counts, *targs, chunk=chunk,
                                        max_len=t.max_len)
        if backend == "scan":
            return decode_chunks_jit(words, counts, *targs, chunk=chunk,
                                     max_len=t.max_len)
        if backend == "multisym":
            return decode_chunks_multisym_jit(
                words, counts, *multisym_table_args(book), chunk=chunk,
                max_len=t.max_len)
        from ..kernels.decode import decode_chunks_multisym_pallas
        return decode_chunks_multisym_pallas(
            words, counts, *multisym_table_args(book, full=False), *targs,
            chunk=chunk, max_len=t.max_len)

    def decode_plane(self, words, book, n_symbols):
        from .encoder import decode_jit
        t = book.tables
        return decode_jit(words, jnp.asarray(t.first_code),
                          jnp.asarray(t.base_index),
                          jnp.asarray(t.num_codes),
                          jnp.asarray(t.sorted_symbols),
                          n_symbols, max_len=t.max_len)


@register_codec
class QLCCodec(Codec):
    """Quad Length Codes: four lengths, 2-leading-bit class, no tables.

    Length assignment is exhaustive search over the ≤ 3060 feasible
    non-decreasing 4-tuples (optimal within the QLC family); decode is
    the branchless window walk — ``scan`` (lax formulation + window-LUT
    symbol resolve) or ``pallas`` (``kernels.decode``).
    """

    name = "qlc"
    backends = ("scan", "pallas")
    default_backend = "scan"

    def build_book(self, counts, *, book_id=-1, key=("", "", ""),
                   max_len=MAX_CODE_LEN, floor=1, n_symbols=None):
        from .qlc import build_qlc_book
        return build_qlc_book(counts, book_id=book_id, key=tuple(key),
                              max_len=max_len, floor=floor,
                              n_symbols=n_symbols)

    def book_from_lengths(self, lengths, *, book_id=-1, key=("", "", ""),
                          max_len=MAX_CODE_LEN):
        from .qlc import qlc_book_from_lengths
        return qlc_book_from_lengths(lengths, book_id=book_id,
                                     key=tuple(key), max_len=max_len)

    def decode_blocks(self, words, counts, book, chunk, backend):
        backend = self.resolve_backend(backend)
        if backend == "pallas":
            from ..kernels.decode import decode_chunks_qlc_pallas
            from .qlc import qlc_kernel_args
            return decode_chunks_qlc_pallas(words, counts,
                                            *qlc_kernel_args(book),
                                            chunk=chunk,
                                            max_len=book.max_len)
        from .qlc import decode_chunks_qlc_jit, qlc_decode_args
        return decode_chunks_qlc_jit(words, counts, *qlc_decode_args(book),
                                     chunk=chunk, max_len=book.max_len)
