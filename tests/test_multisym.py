"""Multi-symbol table-driven decode: table invariants, bit-exactness vs
the pure-Python oracle, and adversarial code-length extremes.

The contract under test: for ANY length-limited canonical codebook and
ANY symbol stream, the ``multisym`` backends (XLA window-replay scan and
the Pallas window-LUT kernel) decode bit-exactly what ``decode_np`` — a
fully independent pure-Python decoder — reads from the same words.
Adversarial shapes pin both ends of the design envelope:

  * all codes at MAX_CODE_LEN (16) bits — every window is longer than
    K, so the decode is slow-path only (``meta`` count 0 everywhere);
  * an alphabet of two 1-bit codes — every window holds s_max symbols,
    the maximum replay amortization.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.codebook import Codebook, build_codebook
from repro.core.encoder import (decode_chunked, decode_chunks_multisym_jit,
                                decode_np, encode_chunked,
                                multisym_table_args)
from repro.core.huffman import (MAX_CODE_LEN, MULTISYM_SMAX,
                                build_multisym_tables, canonical_codes,
                                canonical_decode_tables, kraft_sum)
from repro.kernels import ops, ref
from repro.kernels.decode import decode_chunks_multisym_pallas


def _book_from_lengths(lengths) -> Codebook:
    """A Codebook directly from a length vector (no histogram needed)."""
    lv = np.asarray(lengths, dtype=np.int32)
    return Codebook(book_id=-1, key=("test", "bytes", "b0"), lengths=lv,
                    codes=canonical_codes(lv),
                    tables=canonical_decode_tables(lv),
                    source_counts=np.ones(lv.shape[0], np.int64))


def _random_book(rng) -> Codebook:
    """Random *length-limited* codebook from a random skewed histogram.

    Codec pinned: everything in this file is about the canonical-Huffman
    multisym tables, so the CI codec matrix must not redirect it."""
    counts = np.maximum(rng.integers(0, 10000, size=256) ** 2, 1)
    return build_codebook(counts, codec="huffman")


def _roundtrip_all_backends(sym: np.ndarray, book: Codebook, chunk: int):
    stream = encode_chunked(jnp.asarray(sym), book, chunk=chunk)
    outs = {b: np.asarray(decode_chunked(stream, book, backend=b))
            for b in ("scan", "pallas", "multisym", "multisym_pallas")}
    # independent oracle: the merged stream read by pure Python
    words, total = ops.merge_block_streams(stream.block_words,
                                           stream.block_bits)
    want = decode_np(words, sym.shape[0], book)
    for backend, got in outs.items():
        assert (got == sym).all(), f"{backend}: roundtrip"
        assert (got == want).all(), f"{backend}: != decode_np"


class TestTableBuild:
    def test_table_invariants_random_books(self):
        rng = np.random.default_rng(0)
        for _ in range(5):
            book = _random_book(rng)
            mt = book.multisym_tables()
            cnt = mt.meta & 0xFF
            bits = mt.meta >> 8
            assert mt.syms.shape == (1 << mt.k, mt.s_max)
            assert cnt.max() <= mt.s_max
            assert bits.max() <= mt.k          # never consumes past window
            assert ((cnt > 0) | (bits == 0)).all()
            # meta_full agrees with meta on fast windows and stores the
            # true long-code length on slow ones
            w = np.arange(1 << mt.max_len)
            km = mt.meta[w >> (mt.max_len - mt.k)]
            fast = (km & 0xFF) > 0
            assert (mt.meta_full[fast] == km[fast]).all()
            slow_bits = mt.meta_full[~fast] >> 8
            if slow_bits.size:
                assert slow_bits.min() > mt.k
                assert slow_bits.max() <= mt.max_len

    def test_guaranteed_progress(self):
        # every entry advances ≥1 bit (fast) or defers to a slow length
        rng = np.random.default_rng(1)
        mt = _random_book(rng).multisym_tables()
        cnt = mt.meta_full & 0xFF
        bits = mt.meta_full >> 8
        assert (np.where(cnt > 0, bits, 1) >= 1).all()
        assert (bits[cnt == 0] >= 1).all()

    def test_sym_full_matches_canonical_first_symbol(self):
        book = _random_book(np.random.default_rng(2))
        mt = book.multisym_tables()
        t = book.tables
        # spot-check: window formed by each symbol's own code, zero-padded
        for s in range(0, 256, 17):
            l = int(book.lengths[s])
            w = int(book.codes[s]) << (t.max_len - l)
            assert int(mt.sym_full[w]) == s

    def test_k_bounds_validated(self):
        with pytest.raises(ValueError, match="k must be"):
            build_multisym_tables(np.full(256, 8, np.int32), k=0)
        with pytest.raises(ValueError, match="k must be"):
            build_multisym_tables(np.full(256, 8, np.int32), k=17)


class TestPropertyBitExact:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 5000))
    @settings(max_examples=15, deadline=None)
    def test_random_books_random_streams(self, seed, n):
        rng = np.random.default_rng(seed)
        book = _random_book(rng)
        p = rng.dirichlet(np.full(256, 0.05))
        sym = rng.choice(256, size=n, p=p).astype(np.uint8)
        _roundtrip_all_backends(sym, book, chunk=512)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_adversarial_all_max_length_codes(self, seed):
        # 256 × 16-bit codes: every window's first code overruns K, so
        # every step is slow-path — the worst case the static step bound
        # is sized for.
        rng = np.random.default_rng(seed)
        book = _book_from_lengths(np.full(256, MAX_CODE_LEN, np.int32))
        mt = book.multisym_tables()
        assert ((mt.meta & 0xFF) == 0).all()   # no fast window exists
        sym = rng.integers(0, 256, size=777).astype(np.uint8)
        _roundtrip_all_backends(sym, book, chunk=256)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_adversarial_all_one_bit_codes(self, seed):
        # two 1-bit codes: every window replays s_max symbols — maximum
        # amortization, and the j-slot packing at its limit.
        rng = np.random.default_rng(seed)
        lengths = np.zeros(256, np.int32)
        lengths[:2] = 1
        book = _book_from_lengths(lengths)
        mt = book.multisym_tables()
        assert ((mt.meta & 0xFF) == MULTISYM_SMAX).all()
        sym = rng.integers(0, 2, size=4321).astype(np.uint8)
        _roundtrip_all_backends(sym, book, chunk=2048)

    @pytest.mark.parametrize("chunk", [31, 255, 1001])
    def test_odd_chunk_worst_case_expansion(self, chunk):
        # Regression: odd chunk × all-16-bit codes fills the last
        # capacity word completely; with the old floor-division
        # capacity the decoders' cap-2 window clamp misread the final
        # codewords of every chunk (silent corruption on scan/pallas
        # too, not just multisym).
        rng = np.random.default_rng(chunk)
        book = _book_from_lengths(np.full(256, MAX_CODE_LEN, np.int32))
        sym = rng.integers(0, 256, size=4 * chunk + 7).astype(np.uint8)
        _roundtrip_all_backends(sym, book, chunk=chunk)

    def test_mixed_extreme_lengths(self):
        # one hot symbol at 1 bit, all others at the 16-bit limit (a
        # valid, incomplete prefix code: Kraft = 1/2 + 255/2^16 < 1) —
        # fast and slow paths interleave within single windows.
        lengths = np.full(256, MAX_CODE_LEN, np.int32)
        lengths[0] = 1
        assert kraft_sum(lengths) < 1.0
        book = _book_from_lengths(lengths)
        rng = np.random.default_rng(7)
        sym = np.where(rng.random(6000) < 0.7, 0,
                       rng.integers(0, 256, size=6000)).astype(np.uint8)
        _roundtrip_all_backends(sym, book, chunk=512)


class TestKernelParity:
    def test_pallas_vs_both_oracles(self):
        rng = np.random.default_rng(11)
        book = _random_book(rng)
        sym = rng.integers(0, 256, size=5000).astype(np.uint8)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=512)
        t = book.tables
        counts = jnp.asarray(stream.chunk_counts())
        targs = (jnp.asarray(t.first_code), jnp.asarray(t.base_index),
                 jnp.asarray(t.num_codes), jnp.asarray(t.sorted_symbols))
        got = decode_chunks_multisym_pallas(
            stream.block_words, counts, *multisym_table_args(book, full=False),
            *targs, chunk=512, max_len=t.max_len, interpret=True)
        scan_want = ref.decode_chunks_ref(stream.block_words, counts, *targs,
                                          chunk=512, max_len=t.max_len)
        ms_want = ref.decode_chunks_multisym_ref(
            stream.block_words, counts, *multisym_table_args(book),
            chunk=512, max_len=t.max_len)
        assert (np.asarray(got) == np.asarray(scan_want)).all()
        assert (np.asarray(got) == np.asarray(ms_want)).all()

    def test_ops_wrapper_roundtrip(self):
        rng = np.random.default_rng(13)
        book = _random_book(rng)
        sym = rng.integers(0, 256, size=3000).astype(np.uint8)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=1024)
        out = ops.decode_chunks_multisym(stream.block_words,
                                         stream.chunk_counts(), book,
                                         chunk=1024)
        flat = np.asarray(out).reshape(-1)[:3000]
        assert (flat == sym).all()

    def test_table_size_validation(self):
        rng = np.random.default_rng(17)
        book = _random_book(rng)
        sym = rng.integers(0, 256, size=100).astype(np.uint8)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=128)
        counts = jnp.asarray(stream.chunk_counts())
        bad = jnp.zeros((100,), jnp.int32)    # not a 2^max_len step table
        emit = jnp.zeros((1 << MAX_CODE_LEN,), jnp.int32)
        with pytest.raises(ValueError, match="step_tab"):
            decode_chunks_multisym_jit(stream.block_words, counts, bad,
                                       emit, chunk=128)

    def test_step_tab_packing_consistent(self):
        from repro.core.huffman import STEP_CNT_BITS, STEP_PTR_BITS
        mt = _random_book(np.random.default_rng(29)).multisym_tables()
        ptr = mt.step_tab & ((1 << STEP_PTR_BITS) - 1)
        cnt = (mt.step_tab >> STEP_PTR_BITS) & ((1 << STEP_CNT_BITS) - 1)
        adv = mt.step_tab >> (STEP_PTR_BITS + STEP_CNT_BITS)
        size = 1 << mt.k
        w = np.arange(1 << mt.max_len)
        slow = (mt.meta_full & 0xFF) == 0
        # fast windows point at their LUT row; slow ones at sym_full
        assert (ptr[~slow] == (w[~slow] >> (mt.max_len - mt.k))
                * mt.s_max).all()
        assert (ptr[slow] == size * mt.s_max + w[slow]).all()
        assert (cnt == np.maximum(mt.meta_full & 0xFF, 1)).all()
        assert (adv == mt.meta_full >> 8).all()
        # first emitted symbol always matches the full-window decode
        assert (mt.emit_tab[ptr] == mt.sym_full).all()


class TestBackendDispatch:
    def test_unknown_backend_rejected(self):
        rng = np.random.default_rng(19)
        book = _random_book(rng)
        sym = rng.integers(0, 256, size=64).astype(np.uint8)
        stream = encode_chunked(jnp.asarray(sym), book, chunk=64)
        with pytest.raises(ValueError, match="not supported by codec"):
            decode_chunked(stream, book, backend="turbo")

    def test_spec_accepts_multisym(self):
        from repro.comm.compression import CompressionSpec
        spec = CompressionSpec(mode="bitexact", codec="huffman",
                               decode_backend="multisym")
        assert spec.decode_backend == "multisym"
        with pytest.raises(ValueError, match="not supported by codec"):
            CompressionSpec(codec="huffman", decode_backend="warp")

    def test_spec_carry_validation(self):
        from repro.comm.compression import CompressionSpec
        spec = CompressionSpec(mode="bitexact", transport="ring",
                               carry="f32")
        assert spec.carry == "f32"
        with pytest.raises(ValueError, match="unknown carry"):
            CompressionSpec(carry="f64")
        with pytest.raises(ValueError, match="requires the ring"):
            CompressionSpec(transport="chunked", carry="f32")

    def test_multisym_cache_reused(self):
        book = _random_book(np.random.default_rng(23))
        assert book.multisym_tables() is book.multisym_tables()
        assert book.multisym_tables(k=12) is not book.multisym_tables(k=13)


class TestServeVerifyBackend:
    @pytest.mark.parametrize("backend", ["scan", "multisym"])
    def test_decode_verify_runs_spec_backend(self, backend):
        # the serve decode-verify path must stay lossless (mismatch 0)
        # under every spec decode backend
        import jax
        from repro.comm.compression import CompressionSpec
        from repro.models.common import ModelConfig, BlockGroup
        from repro.models import model_init
        from repro.models.transformer import prefill
        from repro.serve.engine import make_serve_step
        from functools import partial

        cfg = ModelConfig(name="s", arch_type="dense", d_model=32,
                          vocab_size=64, blocks=(BlockGroup(("attn",), 1),),
                          n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                          remat="none")
        params = model_init(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        books = {p: build_codebook(np.maximum(
            np.bincount(rng.integers(0, 256, 4096), minlength=256), 1),
            codec="huffman")
            for p in ("lo", "hi")}
        spec = CompressionSpec.from_books(books, "bf16", mode="bitexact",
                                          decode_backend=backend, chunk=64)
        step = jax.jit(make_serve_step(cfg, spec))
        tokens = jnp.ones((1, 4), jnp.int32)
        logits, caches = jax.jit(partial(prefill, cfg=cfg, cache_len=16))(
            params, {"tokens": tokens})
        _, _, m = step(params, tokens[:, -1:], caches, jnp.int32(4))
        assert float(m["act_decode_mismatch"]) == 0.0
        assert float(m["act_decoded_bits"]) > 0.0
        assert float(m["act_pack_kernel_chunks"]) == 0.0   # CPU: no kernel
