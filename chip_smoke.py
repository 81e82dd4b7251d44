#!/usr/bin/env python3
"""Smoke run of the main path on a TPU — a start-up check, not a benchmark.

    python chip_smoke.py             # one chip: kernels, codec, serve, weights
    python chip_smoke.py --chips 4   # four chips: compressed collectives only

One process drives every phase in order; any failure exits non-zero.
With no TPU (or without the repository's ``src/`` next to this file) it
exits non-zero before doing any work and prints no result.

Phases (one chip):
  kernels  the histogram, LUT-encode and bit-pack Pallas kernels on
           4M symbols, bit-exact against their oracles;
  codec    fixed books for both codecs built from a "previous batch",
           a (4096, 9728) bf16 activation (76 MiB) encoded with
           ``encode_chunked_jit`` and decoded by every backend
           ``CompressionSpec.decode_backend`` accepts, each ``==`` the
           input; a 64k-symbol slice checked word for word vs
           ``encode_np``;
  serve    ``Engine`` on qwen3-4b at its published widths (random
           weights from ``--seed``), batch 4 × 128-token prompts, 16 new
           tokens, ``bitexact`` activation coding with the Pallas decode
           backend: no decode mismatch, fewer coded than raw bits, and
           the same greedy tokens as an engine without compression;
  weights  a ``CompressedParamStore`` of one qwen3-4b FFN leaf (w_down,
           9728 × 2560) per codec: ``materialize`` bit-exact and the
           fused ``decode_matmul`` kernel ``==`` its chunk-order oracle.

Four chips: ring all-reduce, reduce-scatter, all-gather, all-to-all and
the hierarchical 2×2 all-reduce over a (2560, 9728) bf16 payload per
device, against ``psum`` / ``psum_scatter`` / ``all_gather`` /
``all_to_all`` — ``==`` on an integer-valued payload, rtol/atol 5e-2 on
a Gaussian one.

Earlier lines report per-phase wall and compile time; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

FFN_SHAPE = (4096, 9728)        # qwen3-4b: tokens × d_ff activation
GRAD_SHAPE = (2560, 9728)       # qwen3-4b: one w_gate/w_up gradient leaf
KERNEL_SYMBOLS = 1 << 22
CHUNK = 2048


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info(platform: str = "tpu"):
    """The device as JAX reports it; fails unless it is ``platform``."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == platform,
          f"need a {platform} device, JAX found {devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Timer:
    """Wall time of a phase plus the compile time JAX logged inside it."""

    def __enter__(self):
        from jax import monitoring
        self.compile_s = 0.0

        def listener(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        self._listener = listener
        monitoring.register_event_duration_secs_listener(listener)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        self.wall_s = time.perf_counter() - self.t0
        monitoring.unregister_event_duration_listener(self._listener)


def _books(planes, codec):
    """Fixed per-plane books built from host histograms."""
    import numpy as np
    from repro.core.codec import get_codec
    c = get_codec(codec)
    return {p: c.build_book(np.bincount(np.asarray(s).reshape(-1),
                                        minlength=256), key=("smoke", p))
            for p, s in planes.items()}


def _planes(x):
    from repro.core.symbols import SCHEMES
    return SCHEMES["bf16"].to_symbols_jnp(x)


# ---------------------------------------------------------------- phases
def phase_kernels(seed: int, n: int = KERNEL_SYMBOLS) -> dict:
    """Histogram, LUT-encode and lookup-and-pack kernels vs their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.encoder import (_chunk_rows, _masked_rows,
                                    _pack_rows_xla, encode_chunked_jit)
    from repro.kernels import bitpack, ref
    from repro.kernels.encode import encode_lookup_pallas
    from repro.kernels.histogram import histogram256_pallas

    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n,), jnp.float32).astype(jnp.bfloat16)
    sym = _planes(x)["hi"]
    hist = np.asarray(histogram256_pallas(sym))
    check(np.array_equal(hist, np.bincount(np.asarray(sym), minlength=256)),
          "histogram kernel != bincount")
    book = _books({"hi": sym}, "huffman")["hi"]
    lut = jnp.asarray(book.code_lut())
    codes, lens, bits = encode_lookup_pallas(sym, lut)
    wc, wl, wb = ref.encode_lookup_ref(sym, lut)
    check(bool(jnp.array_equal(codes, wc)) and bool(jnp.array_equal(lens, wl))
          and int(bits) == int(wb), "LUT-encode kernel != gather oracle")
    book_codes, book_lens = jnp.asarray(book.codes), jnp.asarray(book.lengths)
    words, block_bits = bitpack.lookup_pack_pallas(sym, book_codes, book_lens)
    want_w, want_b = _pack_rows_xla(
        *_masked_rows(*_chunk_rows(sym, bitpack.BLOCK), book_codes,
                      book_lens), bitpack.CAP_WORDS)
    check(bool(jnp.array_equal(words, want_w))
          and bool(jnp.array_equal(block_bits, want_b)),
          "lookup-and-pack kernel != gather and scatter-add packing")
    enc_w, enc_b = encode_chunked_jit(sym, book_codes, book_lens,
                                      chunk=bitpack.BLOCK)
    check(bool(jnp.array_equal(enc_w, want_w))
          and bool(jnp.array_equal(enc_b, want_b)),
          "encode_chunked_jit != scatter-add packing")
    return {"symbols": n, "coded_bits": int(bits)}


def phase_codec(seed: int, shape=FFN_SHAPE, chunk: int = CHUNK,
                np_slice: int = 1 << 16) -> dict:
    """Fixed books → chunked encode → every decode backend, both codecs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.codec import get_codec
    from repro.core.encoder import (chunk_counts_for, concat_chunks,
                                    encode_chunked_jit, encode_np)

    k_prev, k_new = jax.random.split(jax.random.PRNGKey(seed))
    prev = (jax.random.normal(k_prev, shape, jnp.float32)
            ).astype(jnp.bfloat16)
    x = (jax.random.normal(k_new, shape, jnp.float32)).astype(jnp.bfloat16)
    check(x.nbytes >= 64 << 20 or shape != FFN_SHAPE, "payload under 64 MiB")
    prev_planes = _planes(prev)
    planes = _planes(x)
    n = int(x.size)
    counts = jnp.asarray(chunk_counts_for(n, chunk))
    out = {"payload_bytes": int(x.nbytes)}
    for codec_name in ("huffman", "qlc"):
        codec = get_codec(codec_name)
        books = _books(prev_planes, codec_name)
        coded = 0
        for plane, sym in planes.items():
            book = books[plane]
            codes = jnp.asarray(np.asarray(book.codes, np.uint32))
            lengths = jnp.asarray(np.asarray(book.lengths, np.int32))
            words, bits = encode_chunked_jit(sym, codes, lengths, chunk=chunk)
            coded += int(bits.sum())
            # word-for-word check of the leading chunks vs the NumPy codec
            m = min(np_slice, n) // chunk
            head = np.asarray(sym[:m * chunk]).reshape(m, chunk)
            w_np = np.asarray(words[:m])
            b_np = np.asarray(bits[:m])
            for i in range(m):
                ref_w, ref_bits = encode_np(head[i], book.codes, book.lengths)
                check(int(b_np[i]) == ref_bits and np.array_equal(
                    w_np[i, :len(ref_w)], ref_w),
                    f"{codec_name}/{plane}: chunk {i} != encode_np")
            for backend in codec.backends:
                with Timer() as t:
                    blocks = codec.decode_blocks(words, counts, book, chunk,
                                                 backend)
                    same = bool(jnp.array_equal(
                        concat_chunks(blocks, np.asarray(counts)), sym))
                check(same, f"{codec_name}/{plane}/{backend}: decode != input")
                log(f"codec {codec_name}/{plane}/{backend}: round trip "
                    f"exact, wall {t.wall_s:.2f}s (compile "
                    f"{t.compile_s:.2f}s)")
        out[f"{codec_name}_coded_over_raw"] = coded / (16.0 * n)
    return out


def phase_serve(seed: int, cfg=None, batch: int = 4, prompt_len: int = 128,
                cache_len: int = 256, new_tokens: int = 16) -> dict:
    """Engine with bitexact Pallas-decoded activation coding vs raw."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm.compression import CompressionSpec
    from repro.configs import get_config
    from repro.models import model_init
    from repro.serve.engine import Engine, ServeConfig

    cfg = cfg or get_config("qwen3-4b")
    # jitted, so each leaf's random draw and bf16 cast fuse (no f32 copy)
    params = jax.jit(model_init, static_argnums=0)(cfg,
                                                   jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size,
                                jnp.int32)
    serve_cfg = ServeConfig(max_cache_len=cache_len)
    raw = Engine(params, cfg, serve_cfg)
    toks_raw, _ = raw.generate(prompt, new_tokens)
    # books from the prompt's logits — the "previous batch" of the
    # tensor the serve step codes
    logits, _ = raw._prefill(params, {"tokens": prompt})
    check(bool(jnp.isfinite(logits.astype(jnp.float32)).all()),
          "prefill logits not finite")
    books = _books(_planes(logits[:, -1:].astype(jnp.bfloat16)), "huffman")
    spec = CompressionSpec.from_books(books, "bf16", tensor_kind="act",
                                      mode="bitexact",
                                      decode_backend="pallas")
    coded = Engine(params, cfg, serve_cfg, comp_spec=spec)
    toks, totals = coded.generate(prompt, new_tokens)
    check(totals["act_decode_mismatch"] == 0,
          f"act_decode_mismatch = {totals['act_decode_mismatch']}")
    check(0 < totals["act_coded_bits"] < totals["act_raw_bits"],
          "coded activation bits not below raw")
    check(np.array_equal(toks, toks_raw),
          "coded engine's greedy tokens differ from the raw engine's")
    return {"tokens": int(toks.size),
            "act_coded_over_raw": totals["act_coded_bits"]
            / totals["act_raw_bits"],
            "act_decode_chunks": totals["act_decode_chunks"]}


def phase_weights(seed: int, leaf=None, chunk: int | None = None,
                  m: int = 8) -> dict:
    """Coded FFN leaf: materialize bit-exact, fused matmul == oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.kernels.ref import decode_matmul_ref
    from repro.memstore import CompressedParamStore
    from repro.models.common import Axes
    from repro.models.layers import mlp_init

    if leaf is None:
        cfg = get_config("qwen3-4b")
        leaf = mlp_init(jax.random.PRNGKey(seed), cfg, Axes())["w_down"]
    n_cols = leaf.shape[1]
    chunk = chunk or n_cols
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (m, leaf.shape[0]),
                          jnp.float32).astype(jnp.bfloat16)
    out = {"leaf": list(leaf.shape)}
    for codec in ("huffman", "qlc"):
        store = CompressedParamStore.from_tree({"w": leaf}, codec=codec,
                                               chunk=chunk, min_size=1)
        name = store.names()[0]
        back = store.materialize(name)
        check(np.array_equal(np.asarray(back).view(np.uint16),
                             np.asarray(leaf).view(np.uint16)),
              f"{codec}: materialize != leaf")
        lo, hi, counts = store.plane_blocks(name)
        with Timer() as t:
            got = jax.block_until_ready(store.matmul(x, name))
        want = decode_matmul_ref(x, jnp.asarray(lo), jnp.asarray(hi),
                                 jnp.asarray(counts), store.books,
                                 chunk=chunk, n_cols=n_cols)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"{codec}: decode_matmul != chunk-order oracle")
        log(f"weights {codec}: decode_matmul exact, wall {t.wall_s:.2f}s "
            f"(compile {t.compile_s:.2f}s)")
        out[f"{codec}_coded_over_raw"] = store.footprint()["ratio"]
    return out


def collective_pairs(n: int, books, chunk: int = CHUNK) -> dict:
    """Each coded collective beside its ``jax.lax`` reference.

    name → (mesh axes, coded fn, reference fn), both fns mapping one
    device's block to its result block under ``jax.shard_map``: "flat"
    is a 1-D ("data",) mesh of n devices, "grid" a 2 × n/2
    ("outer", "inner") mesh for the hierarchical ring.
    """
    import jax
    import jax.numpy as jnp
    from repro.comm import (CompressionSpec, all_gather_compressed,
                            all_reduce_compressed, all_to_all_compressed,
                            reduce_scatter_compressed)

    def spec(**kw):
        return CompressionSpec.from_books(books, "bf16", tensor_kind="grad",
                                          mode="bitexact", transport="ring",
                                          chunk=chunk, **kw)

    flat, hier = spec(), spec(axes=("inner", "outer"))

    def split(v):                       # (1, *s) → (n, -1, *s[1:])
        return v.reshape((n, -1) + v.shape[2:])

    return {
        "all_reduce": (
            "flat",
            lambda v: all_reduce_compressed(v, "data", books, flat)[0],
            lambda v: jax.lax.psum(v.astype(jnp.float32), "data")),
        "reduce_scatter": (
            "flat",
            lambda v: reduce_scatter_compressed(v, "data", books,
                                                flat)[0][None],
            lambda v: jax.lax.psum_scatter(v.astype(jnp.float32).reshape(-1),
                                           "data", tiled=True)[None]),
        "all_gather": (
            "flat",
            lambda v: all_gather_compressed(v, "data", books, flat)[0],
            lambda v: jax.lax.all_gather(v, "data", tiled=True)),
        "all_to_all": (
            "flat",
            lambda v: all_to_all_compressed(split(v), "data", books,
                                            flat)[0].reshape(v.shape),
            lambda v: jax.lax.all_to_all(split(v), "data", 0, 0
                                         ).reshape(v.shape)),
        f"hierarchical_2x{n // 2}": (
            "grid",
            lambda v: all_reduce_compressed(v, "inner", books, hier)[0],
            lambda v: jax.lax.psum(jax.lax.psum(v.astype(jnp.float32),
                                                "inner"), "outer")),
    }


def collective_meshes(devices):
    """The "flat" and "grid" meshes of ``collective_pairs`` and the
    partition spec of a block on each."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.asarray(devices)
    n = devs.size
    return {"flat": (Mesh(devs, ("data",)), P("data")),
            "grid": (Mesh(devs.reshape(2, n // 2), ("outer", "inner")),
                     P("outer", "inner"))}


def shard_mapped(mesh, spec, fn):
    import jax
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec))


def block_shape(axes: str, n: int, shape) -> tuple:
    """Global shape of n per-device payloads of ``shape`` on mesh ``axes``."""
    lead = (n,) if axes == "flat" else (2, n // 2)
    return lead + tuple(shape)


def compile_collectives(devices, books, shape=GRAD_SHAPE,
                        chunk: int = CHUNK) -> dict:
    """Compile every coded collective and its reference for ``devices``.

    Each coded collective is one large XLA program (every ring hop's
    decode and re-encode inlined) and the compiles are independent, so
    they run in parallel threads.  Returns (op, "coded" | "ref") → the
    compiled program, taking the payload laid out as ``block_shape``.
    """
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    from jax.sharding import NamedSharding

    n = len(devices)
    meshes = collective_meshes(devices)
    jobs = {}
    for op, (axes, coded_fn, ref_fn) in collective_pairs(
            n, books, chunk).items():
        mesh, spec = meshes[axes]
        arg = jax.ShapeDtypeStruct(block_shape(axes, n, shape), jnp.bfloat16,
                                   sharding=NamedSharding(mesh, spec))
        for name, fn in (("coded", coded_fn), ("ref", ref_fn)):
            jobs[op, name] = (shard_mapped(mesh, spec, fn), arg)
    with ThreadPoolExecutor(len(jobs)) as pool:
        done = list(pool.map(lambda job: job[0].lower(job[1]).compile(),
                             jobs.values()))
    return dict(zip(jobs, done))


def phase_collectives(seed: int, shape=GRAD_SHAPE, chunk: int = CHUNK,
                      n: int = 4) -> dict:
    """Compressed ring collectives on n devices vs the lax collectives."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    devs = jax.devices()[:n]
    check(len(devs) == n, f"need {n} devices, found {len(jax.devices())}")
    meshes = collective_meshes(devs)
    k_prev, k_int, k_gauss = jax.random.split(jax.random.PRNGKey(seed), 3)
    gshape = (n,) + tuple(shape)
    # one book set, from a "previous batch", codes both payloads, so
    # each collective compiles once
    books = _books(_planes(jax.random.normal(k_prev, shape, jnp.float32
                                             ).astype(jnp.bfloat16)),
                   "huffman")
    payloads = {
        "integer": jax.random.randint(k_int, gshape, -2, 3
                                      ).astype(jnp.bfloat16),
        "gaussian": jax.random.normal(k_gauss, gshape, jnp.float32
                                      ).astype(jnp.bfloat16),
    }

    def close(got, want, exact):
        got = np.asarray(got, np.float32).reshape(-1)
        want = np.asarray(want, np.float32).reshape(-1)
        if exact:
            return np.array_equal(got, want)
        return np.allclose(got, want, rtol=5e-2, atol=5e-2)

    with Timer() as t:
        progs = compile_collectives(devs, books, shape, chunk)
    log(f"collectives: {len(progs)} programs compiled in parallel, wall "
        f"{t.wall_s:.2f}s (compile {t.compile_s:.2f}s summed over threads)")
    out = {}
    for op, (axes, _, _) in collective_pairs(n, books, chunk).items():
        mesh, spec = meshes[axes]
        coded, ref = progs[op, "coded"], progs[op, "ref"]
        for kind, x in payloads.items():
            xs = jax.device_put(x.reshape(block_shape(axes, n, shape)),
                                NamedSharding(mesh, spec))
            placed = {s.device for s in xs.addressable_shards}
            check(len(placed) == n, f"{op}: payload on {len(placed)} devices")
            with Timer() as t:
                got = coded(xs)
                ok = close(got, ref(xs), exact=kind == "integer")
            placed = {s.device for s in got.addressable_shards}
            check(len(placed) == n, f"{op}: result on {len(placed)} devices")
            check(ok, f"{op} ({kind}) != lax reference")
            log(f"collectives {op}/{kind}: match, wall {t.wall_s:.2f}s "
                f"(compile {t.compile_s:.2f}s)")
        out[op] = "match"
    return out


PHASES_ONE_CHIP = (("kernels", phase_kernels), ("codec", phase_codec),
                   ("serve", phase_serve), ("weights", phase_weights))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        device = device_info()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    log(f"smoke run (not a benchmark) on {device}; compile cache "
        f"{enable_compile_cache()}")
    phases = (PHASES_ONE_CHIP if args.chips == 1
              else (("collectives", phase_collectives),))
    for name, fn in phases:
        try:
            with Timer() as t:
                res = fn(args.seed)
        except Exception as e:                      # noqa: BLE001
            log(f"phase {name} FAILED: {type(e).__name__}: {e}")
            return 1
        log(f"phase {name}: ok, wall {t.wall_s:.2f}s (compile "
            f"{t.compile_s:.2f}s) {json.dumps(res)}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
