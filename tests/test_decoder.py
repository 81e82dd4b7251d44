"""Pallas chunked decoder: bit-exact parity sweeps vs the jnp oracle.

Covers every symbol scheme's byte planes (the alphabets the paper
analyzes: bf16 planes, f32 bytes, fp8, and the sub-byte eXmY formats),
randomized codebooks (including "foreign" books built from different
data — the paper's fixed-codebook setting), partial tail chunks, and
interop with the Pallas pack kernel's block streams.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.codebook import build_codebook
from repro.core.encoder import (ChunkedStream, decode_chunked,
                                decode_dispatch, decode_np, encode_chunked,
                                encode_chunked_jit, encode_jit)
from repro.core.symbols import SCHEMES
from repro.kernels import ops, ref
from repro.kernels.decode import decode_chunks_pallas


def _book_from(sym, n_symbols=256):
    # codec pinned: this file tests the canonical-Huffman kernels
    # (book.tables, decode_chunks_pallas) regardless of the CI codec leg
    return build_codebook(np.maximum(
        np.bincount(sym, minlength=n_symbols), 1), codec="huffman")


def _decode_both(stream, book):
    """(pallas, ref) decode of a ChunkedStream — both (NB, chunk) blocks."""
    t = book.tables
    counts = jnp.asarray(stream.chunk_counts())
    targs = (jnp.asarray(t.first_code), jnp.asarray(t.base_index),
             jnp.asarray(t.num_codes), jnp.asarray(t.sorted_symbols))
    got = decode_chunks_pallas(stream.block_words, counts, *targs,
                               chunk=stream.chunk, max_len=t.max_len,
                               interpret=True)
    want = ref.decode_chunks_ref(stream.block_words, counts, *targs,
                                 chunk=stream.chunk, max_len=t.max_len)
    return got, want


class TestAllSchemesParity:
    @pytest.mark.parametrize("scheme_name", sorted(SCHEMES))
    def test_every_plane_bit_exact(self, scheme_name):
        scheme = SCHEMES[scheme_name]
        rng = np.random.default_rng(hash(scheme_name) % 2**31)
        x = rng.normal(size=(1200,)).astype(np.float32)
        planes = scheme.to_symbols(x)
        assert set(planes) == set(scheme.planes)
        for plane, sym in planes.items():
            sym = np.asarray(sym, dtype=np.uint8)
            book = _book_from(sym, scheme.n_symbols)
            stream = encode_chunked(jnp.asarray(sym), book, chunk=256)
            got, want = _decode_both(stream, book)
            assert (np.asarray(got) == np.asarray(want)).all(), \
                f"{scheme_name}/{plane}: kernel != ref"
            out = decode_chunked(stream, book, backend="pallas")
            assert (np.asarray(out) == sym).all(), \
                f"{scheme_name}/{plane}: roundtrip"

    @pytest.mark.parametrize("scheme_name", ["bf16", "e4m3", "e2m1"])
    def test_foreign_book_lossless(self, scheme_name):
        # Codebook from batch k, data from batch k+1 (the paper's mode).
        scheme = SCHEMES[scheme_name]
        rng = np.random.default_rng(5)
        prev = rng.normal(size=(2000,)).astype(np.float32)
        x = 1.5 * rng.normal(size=(1500,)).astype(np.float32)
        for plane in scheme.planes:
            book = _book_from(np.asarray(scheme.to_symbols(prev)[plane],
                                         np.uint8), scheme.n_symbols)
            sym = np.asarray(scheme.to_symbols(x)[plane], np.uint8)
            stream = encode_chunked(jnp.asarray(sym), book, chunk=512)
            out = decode_chunked(stream, book, backend="pallas")
            assert (np.asarray(out) == sym).all()


class TestRandomizedCodebooks:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 3000))
    @settings(max_examples=15, deadline=None)
    def test_property_parity_and_roundtrip(self, seed, n):
        rng = np.random.default_rng(seed)
        # randomized book: built from a *different* skewed distribution
        book = build_codebook(np.maximum(
            rng.integers(0, 1000, size=256), 1), codec="huffman")
        p = rng.dirichlet(np.full(256, 0.05))
        sym = rng.choice(256, size=n, p=p).astype(np.uint8)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=512)
        got, want = _decode_both(stream, book)
        assert (np.asarray(got) == np.asarray(want)).all()
        out = decode_chunked(stream, book, backend="pallas")
        assert (np.asarray(out) == sym).all()

    def test_scan_backend_matches_pallas(self):
        rng = np.random.default_rng(7)
        sym = rng.integers(0, 256, size=5000).astype(np.uint8)
        book = _book_from(sym)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=1024)
        a = decode_chunked(stream, book, backend="pallas")
        b = decode_chunked(stream, book, backend="scan")
        assert (np.asarray(a) == np.asarray(b)).all()


class TestChunkedFormat:
    @pytest.mark.parametrize("n", [1, 255, 2048, 2049, 4096, 6001])
    def test_tail_chunk_sizes(self, n):
        rng = np.random.default_rng(n)
        sym = rng.integers(0, 256, size=n).astype(np.uint8)
        book = _book_from(sym)
        stream = encode_chunked(jnp.asarray(sym), book)
        assert stream.n_symbols == n
        assert int(stream.chunk_counts().sum()) == n
        out = decode_chunked(stream, book, backend="pallas")
        assert out.shape == (n,)
        assert (np.asarray(out) == sym).all()

    def test_payload_bits_match_monolithic(self):
        rng = np.random.default_rng(11)
        sym = rng.integers(0, 256, size=7000).astype(np.uint8)
        book = _book_from(sym)
        stream = encode_chunked(jnp.asarray(sym), book)
        _, n_bits = encode_jit(jnp.asarray(sym), jnp.asarray(book.codes),
                               jnp.asarray(book.lengths))
        assert stream.payload_bits() == int(n_bits)
        assert stream.header_bits() == 32 * stream.n_chunks

    def test_merged_chunks_decode_with_np_oracle(self):
        # Stitch the per-chunk streams; the independent pure-Python
        # decoder must read the merged stream back verbatim.
        rng = np.random.default_rng(13)
        sym = rng.integers(0, 256, size=4500).astype(np.uint8)
        book = _book_from(sym)
        stream = encode_chunked(jnp.asarray(sym), book)
        words, total = ops.merge_block_streams(stream.block_words,
                                               stream.block_bits)
        assert total == stream.payload_bits()
        out = decode_np(words, sym.shape[0], book)
        assert (out == sym).all()

    def test_pack_kernel_stream_interop(self):
        # The Pallas pack kernel's block streams ARE the chunked wire
        # format: the decoder consumes them directly.
        rng = np.random.default_rng(17)
        sym = rng.integers(0, 256, size=5000).astype(np.uint8)
        book = _book_from(sym)
        from repro.kernels.bitpack import lookup_pack_pallas
        kw, kb = lookup_pack_pallas(jnp.asarray(sym), jnp.asarray(book.codes),
                                    jnp.asarray(book.lengths))
        stream = encode_chunked(jnp.asarray(sym), book)
        assert (np.asarray(kw) == np.asarray(stream.block_words)).all()
        assert (np.asarray(kb) == np.asarray(stream.block_bits)).all()
        out = ops.decode_with_book_kernel((kw, kb), book, sym.shape[0])
        assert (np.asarray(out) == sym).all()


class TestDispatch:
    def test_dispatch_routes_chunked_and_monolithic(self):
        rng = np.random.default_rng(19)
        sym = rng.integers(0, 256, size=3000).astype(np.uint8)
        book = _book_from(sym)
        stream = encode_chunked(jnp.asarray(sym), book)
        assert isinstance(stream, ChunkedStream)
        a = decode_dispatch(stream, book)
        words, _ = encode_jit(jnp.asarray(sym), jnp.asarray(book.codes),
                              jnp.asarray(book.lengths))
        b = decode_dispatch(words, book, n_symbols=3000)
        assert (np.asarray(a) == sym).all()
        assert (np.asarray(b) == sym).all()

    def test_dispatch_monolithic_requires_count(self):
        rng = np.random.default_rng(23)
        sym = rng.integers(0, 256, size=100).astype(np.uint8)
        book = _book_from(sym)
        words, _ = encode_jit(jnp.asarray(sym), jnp.asarray(book.codes),
                              jnp.asarray(book.lengths))
        with pytest.raises(ValueError):
            decode_dispatch(words, book)
