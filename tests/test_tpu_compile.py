"""Every Pallas kernel compiles through Mosaic for a TPU v5e at real widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on a CPU-only host.  Nothing
executes: each test lowers one kernel entry point with ``interpret=False``
against ``ShapeDtypeStruct`` arguments placed on one described v5e chip
and compiles it, which raises whatever Mosaic would refuse on the chip
(unaligned blocks, vector gathers, unsupported reshapes).

Widths: 4M symbols in chunks of 2048 for the encode/decode kernels, the
serve cell's logits (16 × 151936 symbols, 1187 chunks of 2048) for the
lookup-and-pack kernel as the chunked encoders call it, and qwen3-4b's ``w_down`` (9728 × 2560, one chunk per weight row) for the
fused decode-matmul.  The topology is described inside a fixture, never
at import, so every test worker collects the same tests.
"""
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.encoder import (DEFAULT_CHUNK, _block_rows, _chunk_rows,
                                _encode_rows_kernel, chunk_capacity_words)
from repro.core.huffman import MAX_CODE_LEN, MULTISYM_K, MULTISYM_SMAX
from repro.kernels.decode import (decode_chunks_multisym_pallas,
                                  decode_chunks_pallas,
                                  decode_chunks_qlc_pallas)
from repro.kernels.decode_matmul import (decode_matmul_pallas,
                                         decode_matmul_qlc_pallas)
from repro.kernels.encode import encode_lookup_pallas
from repro.kernels.histogram import histogram256_pallas

N_SYMBOLS = 1 << 22
NB = N_SYMBOLS // DEFAULT_CHUNK
CAP = chunk_capacity_words(DEFAULT_CHUNK)
TLEN = MAX_CODE_LEN + 1
W_DOWN = (9728, 2560)           # qwen3-4b FFN down projection (K, N)
M = 8                           # decode-step rows of x
SERVE_NB = -(-16 * 151936 // DEFAULT_CHUNK)   # qwen3-4b logits a step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:               # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            finally:
                # Loading the TPU library installs a crash handler that
                # prints a stack trace when the process is terminated; a
                # test worker ended by a time limit should end as quietly
                # as the others, without writing into pytest's progress.
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → an abstract argument on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def compiles_with_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_histogram(spec):
    compiles_with_mosaic(
        lambda s: histogram256_pallas(s, interpret=False),
        spec((N_SYMBOLS,), jnp.uint8))


def test_encode_lookup(spec):
    compiles_with_mosaic(
        lambda s, lut: encode_lookup_pallas(s, lut, interpret=False),
        spec((N_SYMBOLS,), jnp.uint8), spec((256, 2), jnp.uint32))


def _book(spec):
    return spec((256,), jnp.uint32), spec((256,), jnp.int32)


def test_bitpack(spec):
    """The lookup-and-pack kernel as ``encode_chunked_jit`` calls it on a
    TPU, at the serve cell's plane: 1187 rows of 2048 symbols."""
    fn = jax.jit(lambda s, c, l: _encode_rows_kernel(
        *_chunk_rows(s, DEFAULT_CHUNK), c, l, CAP, interpret=False))
    sym = spec((SERVE_NB * DEFAULT_CHUNK,), jnp.uint8)
    words, bits = jax.eval_shape(fn, sym, *_book(spec))
    assert words.shape == (SERVE_NB, CAP) and bits.shape == (SERVE_NB,)
    compiles_with_mosaic(fn, sym, *_book(spec))


def test_bitpack_recode(spec):
    """The same kernel as ``recode_chunks_jit`` calls it: blocked symbols
    with a count a block."""
    fn = jax.jit(lambda s, n, c, l: _encode_rows_kernel(
        *_block_rows(s, n), c, l, CAP, interpret=False))
    args = (spec((SERVE_NB, DEFAULT_CHUNK), jnp.int32),
            spec((SERVE_NB,), jnp.int32), *_book(spec))
    words, bits = jax.eval_shape(fn, *args)
    assert words.shape == (SERVE_NB, CAP) and bits.shape == (SERVE_NB,)
    compiles_with_mosaic(fn, *args)


def _canonical_tables(spec, planes=()):
    t = spec(planes + (TLEN,), jnp.int32)
    return t, t, t, spec(planes + (256,), jnp.int32)


def test_decode_canonical(spec):
    compiles_with_mosaic(
        lambda w, c, *t: decode_chunks_pallas(w, c, *t, interpret=False),
        spec((NB, CAP), jnp.uint32), spec((NB,), jnp.int32),
        *_canonical_tables(spec))


def test_decode_multisym(spec):
    compiles_with_mosaic(
        lambda w, c, st, mt, *t: decode_chunks_multisym_pallas(
            w, c, st, mt, *t, interpret=False),
        spec((NB, CAP), jnp.uint32), spec((NB,), jnp.int32),
        spec((1 << MULTISYM_K, MULTISYM_SMAX), jnp.int32),
        spec((1 << MULTISYM_K,), jnp.int32), *_canonical_tables(spec))


def test_decode_qlc(spec):
    compiles_with_mosaic(
        lambda w, c, lp, bp, st: decode_chunks_qlc_pallas(
            w, c, lp, bp, st, interpret=False),
        spec((NB, CAP), jnp.uint32), spec((NB,), jnp.int32),
        spec((), jnp.uint32), spec((), jnp.uint32), spec((256,), jnp.int32))


def _weight_args(spec):
    k, n = W_DOWN
    words = spec((k, chunk_capacity_words(n)), jnp.uint32)
    return spec((M, k), jnp.bfloat16), words, words, spec((k,), jnp.int32)


@pytest.mark.parametrize("codec", ["huffman", "qlc"])
def test_decode_matmul(spec, codec):
    n = W_DOWN[1]
    if codec == "huffman":
        fn = lambda x, lo, hi, c, *t: decode_matmul_pallas(   # noqa: E731
            x, lo, hi, c, *t, chunk=n, n_cols=n, interpret=False)
        tables = _canonical_tables(spec, (2,))
    else:
        fn = lambda x, lo, hi, c, *t: decode_matmul_qlc_pallas(  # noqa: E731
            x, lo, hi, c, *t, chunk=n, n_cols=n, interpret=False)
        tables = (spec((2,), jnp.uint32), spec((2,), jnp.uint32),
                  spec((2, 256), jnp.int32))
    compiles_with_mosaic(fn, *_weight_args(spec), *tables)
