"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core.codebook import build_codebook
from repro.core.encoder import decode_np
from repro.kernels import ops, ref
from repro.kernels.encode import encode_lookup_pallas
from repro.kernels.histogram import histogram256_pallas

SIZES = [1, 7, 128, 4096, 4097, 12_288, 65_536 + 3]
DTYPES = [jnp.uint8, jnp.int32]


def _sym(seed, n, dtype=jnp.uint8, skew=0.05):
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.full(256, skew))
    return jnp.asarray(rng.choice(256, size=n, p=p), dtype=dtype)


def _lut(seed):
    rng = np.random.default_rng(seed)
    counts = np.maximum(rng.integers(0, 1000, size=256), 1)
    # codec pinned: decode_np below walks the canonical prefix tree
    book = build_codebook(counts, codec="huffman")
    return book, jnp.asarray(book.code_lut())


class TestHistogramKernel:
    @pytest.mark.parametrize("n", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        sym = _sym(n, n, dtype)
        got = histogram256_pallas(sym, interpret=True)
        want = ref.histogram256_ref(sym)
        assert_allclose(np.asarray(got), np.asarray(want))

    def test_total_is_n(self):
        sym = _sym(0, 5000)
        assert int(histogram256_pallas(sym, interpret=True).sum()) == 5000

    @given(st.integers(0, 2**31 - 1), st.integers(1, 3000))
    @settings(max_examples=10, deadline=None)
    def test_property(self, seed, n):
        sym = _sym(seed, n)
        got = histogram256_pallas(sym, interpret=True)
        want = np.bincount(np.asarray(sym), minlength=256)
        assert (np.asarray(got) == want).all()


class TestEncodeKernel:
    @pytest.mark.parametrize("n", SIZES)
    def test_matches_ref(self, n):
        sym = _sym(n + 1, n)
        _, lut = _lut(n)
        gc, gl, gb = encode_lookup_pallas(sym, lut, interpret=True)
        wc, wl, wb = ref.encode_lookup_ref(sym, lut)
        assert (np.asarray(gc) == np.asarray(wc)).all()
        assert (np.asarray(gl) == np.asarray(wl)).all()
        assert int(gb) == int(wb)

    def test_all_symbols_exact(self):
        # Every symbol value through the MXU one-hot path, exactly.
        sym = jnp.arange(256, dtype=jnp.uint8)
        book, lut = _lut(9)
        gc, gl, gb = encode_lookup_pallas(sym, lut, interpret=True)
        assert (np.asarray(gc) == book.codes).all()
        assert (np.asarray(gl) == book.lengths).all()

    def test_kernel_pack_roundtrips(self):
        sym = _sym(5, 2048)
        book, _ = _lut(5)
        res = ops.encode_with_book(sym, book)
        out = decode_np(np.asarray(res.words), 2048, book)
        assert (out == np.asarray(sym)).all()

    def test_kernel_pack_matches_core_encoder(self):
        from repro.core.encoder import encode_jit
        sym = _sym(6, 1536)
        book, _ = _lut(6)
        res = ops.encode_with_book(sym, book)
        words, n_bits = encode_jit(sym, jnp.asarray(book.codes),
                                   jnp.asarray(book.lengths))
        assert int(res.n_bits) == int(n_bits)
        assert (np.asarray(res.words) == np.asarray(words)).all()

    def test_message_bits_matches_exact(self):
        sym = _sym(7, 10_000)
        book, _ = _lut(7)
        got = ops.message_bits(sym, book.lengths)
        want = book.encoded_bits(np.bincount(np.asarray(sym), minlength=256))
        assert int(got) == want


class TestBitpackKernel:
    @pytest.mark.parametrize("n", [1, 100, 2048, 2049, 5000, 16384])
    def test_block_pack_merge_matches_encoder(self, n):
        from repro.core.encoder import encode_jit
        sym = _sym(n + 40, n)
        book, _ = _lut(n + 40)
        got_words, got_bits = ops.pack_with_book(sym, book)
        want_words, want_bits = encode_jit(sym, jnp.asarray(book.codes),
                                           jnp.asarray(book.lengths))
        assert int(got_bits) == int(want_bits)
        nw = (int(want_bits) + 31) // 32
        assert (np.asarray(got_words)[:nw]
                == np.asarray(want_words)[:nw]).all()

    def test_block_pack_roundtrips_via_decoder(self):
        sym = _sym(77, 6000)
        book, _ = _lut(77)
        words, bits = ops.pack_with_book(sym, book)
        out = decode_np(np.asarray(words), 6000, book)
        assert (out == np.asarray(sym)).all()

    @given(st.integers(0, 2**31 - 1), st.integers(1, 700))
    @settings(max_examples=10, deadline=None)
    def test_property_block_pack(self, seed, n):
        from repro.core.encoder import encode_jit
        sym = _sym(seed, n)
        book, _ = _lut(seed)
        got_words, got_bits = ops.pack_with_book(sym, book)
        _, want_bits = encode_jit(sym, jnp.asarray(book.codes),
                                  jnp.asarray(book.lengths))
        assert int(got_bits) == int(want_bits)
        out = decode_np(np.asarray(got_words), n, book)
        assert (out == np.asarray(sym)).all()

    @staticmethod
    def _rows(case):
        """(sym, valid, codes, lengths, cap) as the chunked encoders hand
        them to ``_encode_rows``: a flat stream with a partial tail chunk,
        recoded blocks with ragged counts (one of them 0, one 1), or a
        flat stream that hits every one of the 256 book entries, under a
        Huffman, a QLC or an all-16-bit book."""
        from repro.core import encoder
        from repro.core.qlc import build_qlc_book
        from repro.kernels.bitpack import BLOCK
        layout, book_kind = case.split("-")
        rng = np.random.default_rng(0)
        counts = np.maximum(rng.integers(0, 1000, size=256), 1)
        counts[:4] = 10 ** 6                    # skew: short and long codes
        if book_kind == "huffman":
            book = build_codebook(counts, codec="huffman")
            codes, lengths = book.codes, book.lengths
        elif book_kind == "qlc":
            book = build_qlc_book(counts)
            codes, lengths = book.codes, book.lengths
        else:                                   # every code 16 bits wide
            codes = rng.integers(0, 1 << 16, size=256)
            lengths = np.full(256, 16)
        codes = jnp.asarray(np.asarray(codes, np.uint32))
        lengths = jnp.asarray(np.asarray(lengths, np.int32))
        if layout == "flat":
            sym, valid = encoder._chunk_rows(_sym(1, 3 * BLOCK + 517), BLOCK)
        elif layout == "allsym":
            every = rng.permutation(np.tile(np.arange(256), 2 * BLOCK // 256))
            sym, valid = encoder._chunk_rows(jnp.asarray(every, jnp.uint8),
                                             BLOCK)
            assert set(np.asarray(sym).ravel()) == set(range(256))
        else:
            blocks = _sym(2, 5 * BLOCK).reshape(5, BLOCK)
            ragged = jnp.asarray([BLOCK, 0, 700, 1, BLOCK], jnp.int32)
            sym, valid = encoder._block_rows(blocks, ragged)
        return sym, valid, codes, lengths, encoder.chunk_capacity_words(BLOCK)

    @pytest.mark.parametrize("case", ["flat-huffman", "flat-qlc",
                                      "ragged-huffman", "ragged-qlc",
                                      "flat-all16", "ragged-all16",
                                      "allsym-huffman", "allsym-qlc",
                                      "allsym-all16"])
    def test_pack_rows_kernel_matches_scatter(self, case):
        """The lookup-and-pack kernel against the gather and scatter-add
        path, words and bits bit for bit."""
        from repro.core import encoder
        sym, valid, codes, lengths, cap = self._rows(case)
        got_words, got_bits = encoder._encode_rows_kernel(
            sym, valid, codes, lengths, cap, interpret=True)
        want_words, want_bits = encoder._pack_rows_xla(
            *encoder._masked_rows(sym, valid, codes, lengths), cap)
        assert got_words.shape == want_words.shape == (sym.shape[0], cap)
        assert (np.asarray(got_bits) == np.asarray(want_bits)).all()
        assert (np.asarray(got_words) == np.asarray(want_words)).all()

    @pytest.mark.parametrize("entry", ["encode_chunked_jit",
                                       "recode_chunks_jit"])
    def test_chunked_encoders_route_alike(self, entry, monkeypatch):
        """The chunked encoders give the same words whichever path
        ``pack_path`` picks: the kernel path forced here runs the
        lookup-and-pack kernel in interpret mode."""
        from repro.core import encoder
        from repro.kernels.bitpack import BLOCK
        book, _ = _lut(3)
        codes, lengths = jnp.asarray(book.codes), jnp.asarray(book.lengths)
        fn = getattr(encoder, entry).__wrapped__     # past jit's caches
        kw = {}
        if entry == "encode_chunked_jit":
            args, kw = (_sym(3, 2 * BLOCK + 300), codes, lengths), {
                "chunk": BLOCK}
        else:
            counts = jnp.asarray([BLOCK, 1, 0], jnp.int32)
            args = (_sym(4, 3 * BLOCK).reshape(3, BLOCK), counts, codes,
                    lengths)
        out = {}
        for path in ("xla", "kernel"):
            monkeypatch.setattr(encoder, "pack_path", lambda w, b: path)
            call = jax.jit(lambda *a: fn(*a, **kw))
            traced = str(jax.make_jaxpr(call)(*args))
            assert ("pallas_call" in traced) == (path == "kernel")
            out[path] = call(*args)
        assert (np.asarray(out["kernel"][0]) == np.asarray(out["xla"][0])).all()
        assert (np.asarray(out["kernel"][1]) == np.asarray(out["xla"][1])).all()

    @pytest.mark.parametrize("backend,width,path", [
        ("tpu", 2048, "kernel"), ("cpu", 2048, "xla"), ("gpu", 2048, "xla"),
        ("tpu", 1024, "xla"), ("tpu", 4096, "xla"), ("tpu", 64, "xla")])
    def test_pack_path_rule(self, backend, width, path):
        from repro.core.encoder import pack_path
        assert pack_path(width, backend) == path

