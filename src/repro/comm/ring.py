"""Ring-compressed collectives: the payload stays Huffman-coded on every hop.

The monolithic/chunked transports ship each shard's stream to the
endpoint (XLA ``all_gather``), so per-hop link bandwidth is only reduced
in the ledger's accounting.  This module implements the hardware-shaped
alternative the paper's encoder is built for (and ZipCCL-style
compressed collectives realize): ``jax.lax.ppermute`` rings over
``ChunkedStream`` words where **every hop**

    decode (chunked canonical walk / Pallas kernel / multisym LUT)
      → reduce (add for reduce-type ops, append/forward for gather-type)
        → re-encode before forwarding

so every wire transfer carries coded bits and the ledger records the
*measured* per-hop traffic instead of an analytic estimate.  The full
collective family:

  ``ring_all_gather``      n−1 hops, forwards unchanged symbols
  ``ring_reduce_scatter``  n−1 fused decode→add→re-encode hops; device i
                           ends owning segment i of the global sum
  ``ring_all_reduce``      reduce-scatter phase + all-gather phase,
                           2(n−1) hops
  ``ring_all_to_all``      n−1 rotated-permutation rounds; each shard
                           leaves its source exactly once (the MoE
                           dispatch wire)

Every hop runs the **fused hop codec**: the decoder's (NB, chunk)
symbol blocks feed the ``recode_chunks_jit`` block fast path directly —
decode → reduce → re-encode is one region of the lowered program with
no flatten/pad/re-chunk of the full symbol stream in between.  Gather
hops forward unchanged symbols, so their blocks recode as-is; reduce
hops add the local partial-sum contribution on the *padded block
layout* (pad slots decode to value 0 and re-mask on encode) and recode
the updated blocks.  The fixed codebook is what makes either viable: no
codebook rides the wire and re-encoding is a single LUT pass (the
paper's single-stage property, per hop).  The decode side is selected
by ``decode_backend``, resolved per codec by ``transport.decode_blocks``
(huffman: ``scan`` / ``pallas`` / ``multisym`` / ``multisym_pallas``;
qlc: ``scan`` / ``pallas`` — ``"auto"`` picks the codec's default, see
docs/codecs.md).  The encode side is codec-agnostic: both codecs pack
through the same ``_encode_rows`` core, so the hop recode path is
unchanged.

Numerics: gather-type ops (all_gather, all_to_all) forward values
unchanged, so they are bit-exact for any input.  Reduce-type ops
accumulate partial sums in the scheme's wire dtype by default
(``carry="wire"`` — a real compressed ring reduces in the link dtype);
the ring-order summation is bit-exact vs ``jax.lax.psum`` /
``psum_scatter`` whenever the additions are exact in that dtype (e.g.
integer-valued payloads — see tests) and agrees to normal
floating-point reordering tolerance otherwise.  ``carry="f32"`` keeps
the partial sums in float32 across hops for training-grade accuracy:
each hop ships the running sum as **two** wire-dtype components (the
rounded value plus its residual), doubling hop payload — the ledger
measures exactly that 2×.

Stats follow the transport convention (replicated scalars = global/n so
a caller psum recovers the global number) plus ring-only keys:
``hop_coded_bits`` ((hops,) measured coded bits per hop, global/n) and
``hops`` (also global/n: psum it to read the hop count, like every
other stat).  For the gather-type ops the re-encoded streams are
bit-identical to the originals, so total coded wire bits equal the
endpoint transports' analytic accounting exactly; for the reduce-type
ops the hops carry *partial sums* whose coded size under the fixed
codebook differs from the inputs' — that measured number is the honest
ring cost.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.codebook import Codebook
from ..core.encoder import (DEFAULT_CHUNK, chunk_counts_for, concat_chunks,
                            recode_chunks_jit)
from ..core.symbols import SCHEMES
from .compression import histogram256_xla
from .transport import decode_blocks, encode_planes, reassemble

__all__ = ["ring_all_gather", "ring_all_reduce", "ring_reduce_scatter",
           "ring_all_to_all", "RING_CARRIES", "DEFAULT_RING_BACKEND"]

RING_CARRIES = ("wire", "f32")
# "auto" resolves per codec inside decode_blocks: the hop codec follows
# whatever codec built the books (huffman → the pure-XLA multisym walk,
# qlc → the branchless scan — both shard_map-safe, docs/codecs.md).
DEFAULT_RING_BACKEND = "auto"


def _fwd_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _bits_sum(enc) -> jnp.ndarray:
    out = jnp.zeros((), jnp.float32)
    for words_bits in enc.values():
        out = out + words_bits[1].astype(jnp.float32).sum()
    return out


def _coded_payload_bits(x, books: Dict[str, Codebook], scheme_name: str
                        ) -> jnp.ndarray:
    """Exact coded size of the local payload (histogram · lengths) —
    equals the summed encoded bit counts without materializing streams."""
    coded = jnp.zeros((), jnp.float32)
    for plane, sym in SCHEMES[scheme_name].to_symbols_jnp(x).items():
        hist = histogram256_xla(sym).astype(jnp.float32)
        coded = coded + jnp.dot(hist, jnp.asarray(books[plane].lengths,
                                                  jnp.float32))
    return coded


def _stack_hops(hop_coded) -> jnp.ndarray:
    return (jnp.stack(hop_coded) if hop_coded
            else jnp.zeros((0,), jnp.float32))


def ring_all_gather(x, axis_name: str, books: Dict[str, Codebook],
                    scheme_name: str = "bf16", *, chunk: int = DEFAULT_CHUNK,
                    decode_backend: str = DEFAULT_RING_BACKEND
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """All-gather over a ppermute ring; every hop decodes and re-encodes.

    Hop h forwards the stream received at hop h−1 (starting with the
    local shard's own stream).  The incoming chunk is decoded on device
    (appended to the gathered result) and re-encoded via the fused hop
    codec — the decoder's blocks go straight into ``recode_chunks_jit``
    — before the next forward; the wire never carries raw symbols.
    Because the codebook is fixed and the codec lossless, the re-encoded
    stream is bit-identical to the original, so summed hop traffic
    equals the monolithic transport's coded wire bits exactly;
    ``hop_coded_bits`` additionally exposes the per-hop breakdown a
    link-level roofline needs.
    """
    n = jax.lax.axis_size(axis_name)
    scheme = SCHEMES[scheme_name]
    planes0 = scheme.to_symbols_jnp(x)
    n_sym = next(iter(planes0.values())).shape[0]
    eff_chunk = max(1, min(chunk, n_sym))
    counts_np = chunk_counts_for(n_sym, eff_chunk)
    counts = jnp.asarray(counts_np)
    nb = int(counts_np.shape[0])
    perm = _fwd_perm(n)

    cur = {plane: (words, bits) for plane, (words, bits, _) in
           encode_planes(x, books, scheme_name, chunk=eff_chunk).items()}
    payload_coded = jax.lax.psum(_bits_sum(cur), axis_name)

    # rel[plane][h] = symbols of the shard that originated h hops upstream
    rel = {plane: [sym.astype(jnp.uint8)] for plane, sym in planes0.items()}
    hop_coded = []
    for _ in range(n - 1):
        hop_coded.append(jax.lax.psum(_bits_sum(cur), axis_name) / n)
        nxt = {}
        for plane, (words, _) in cur.items():
            rw = jax.lax.ppermute(words, axis_name, perm)
            blocks = decode_blocks(rw, counts, books[plane], eff_chunk,
                                   decode_backend)
            rel[plane].append(concat_chunks(blocks, counts_np))
            b = books[plane]
            nxt[plane] = recode_chunks_jit(blocks, counts,
                                           jnp.asarray(b.codes),
                                           jnp.asarray(b.lengths),
                                           max_len=b.max_len)
        cur = nxt

    # hop-relative → absolute shard order: rel[h] came from device (i−h)%n
    idx = (jax.lax.axis_index(axis_name) - jnp.arange(n)) % n
    out_planes = {plane: jnp.take(jnp.stack(lst), idx, axis=0).reshape(-1)
                  for plane, lst in rel.items()}
    y = reassemble(out_planes, scheme_name,
                   (n * x.shape[0],) + x.shape[1:], x.dtype)

    raw = jnp.float32(x.size * scheme.total_symbol_bits()) * n
    coded_wire = sum(hop_coded, jnp.zeros((), jnp.float32))
    stats = {"raw_wire_bits": raw * (n - 1) / n,
             "coded_wire_bits": coded_wire,
             "payload_raw_bits": raw,
             "payload_coded_bits": payload_coded,
             "payload_header_bits": jnp.float32(32.0 * nb * len(cur) * (n - 1)),
             "hop_coded_bits": _stack_hops(hop_coded),
             "hops": jnp.float32(n - 1) / n}
    return y, stats


class _SegmentRing:
    """Shared geometry + fused hop codec for the segment-based ring ops.

    Splits the flat local tensor into n ``seg_len`` segments (the last
    zero-padded to a whole number of chunks) and provides the per-hop
    encode / ppermute-decode / reassemble steps that
    ``ring_reduce_scatter`` and ``ring_all_reduce`` compose.  ``carry``
    selects the accumulation dtype across hops: ``"wire"`` reduces in
    the scheme dtype; ``"f32"`` ships each hop as two wire-dtype
    components (rounded value + residual) and accumulates in float32.
    """

    def __init__(self, x, axis_name: str, books: Dict[str, Codebook],
                 scheme_name: str, chunk: int, decode_backend: str,
                 carry: str):
        if carry not in RING_CARRIES:
            raise ValueError(f"unknown carry {carry!r}; one of "
                             f"{RING_CARRIES}")
        self.axis_name = axis_name
        self.books = books
        self.scheme_name = scheme_name
        self.scheme = SCHEMES[scheme_name]
        self.decode_backend = decode_backend
        self.carry = carry
        self.dtype = x.dtype
        self.n = jax.lax.axis_size(axis_name)
        self.size = x.size
        self.seg_len = -(-self.size // self.n)
        self.acc_dtype = jnp.float32 if carry == "f32" else x.dtype
        self.ncomp = 2 if carry == "f32" else 1
        flat = x.reshape(-1).astype(self.acc_dtype)
        if self.n * self.seg_len > self.size:
            flat = jnp.concatenate(
                [flat, jnp.zeros((self.n * self.seg_len - self.size,),
                                 self.acc_dtype)])
        self.acc = flat.reshape(self.n, self.seg_len)
        self.i = jax.lax.axis_index(axis_name)
        self.perm = _fwd_perm(self.n)
        self.eff_chunk = max(1, min(chunk, self.seg_len))
        self.counts_np = chunk_counts_for(self.seg_len, self.eff_chunk)
        self.counts = jnp.asarray(self.counts_np)
        self.nb = int(self.counts_np.shape[0])
        self.pad_len = self.nb * self.eff_chunk

    # ---------------------------------------------------------- helpers
    def pad_seg(self, seg):
        if self.pad_len == self.seg_len:
            return seg
        return jnp.concatenate(
            [seg, jnp.zeros((self.pad_len - self.seg_len,), seg.dtype)])

    def local_seg(self, idx):
        """Padded local copy of segment ``idx % n`` in the carry dtype."""
        return self.pad_seg(jnp.take(self.acc, idx % self.n, axis=0))

    def to_comps(self, vals):
        """Padded acc-dtype values → wire-dtype hop components."""
        if self.carry == "wire":
            return (vals,)
        hi = vals.astype(self.dtype)
        lo = (vals - hi.astype(jnp.float32)).astype(self.dtype)
        return (hi, lo)

    def from_comps(self, comps):
        if self.carry == "wire":
            return comps[0]
        return comps[0].astype(jnp.float32) + comps[1].astype(jnp.float32)

    def encode_cur(self, vals):
        """Fused-side encode: planes extracted per component on the
        padded layout, packed by the block recode path (pad slots carry
        zero bits via the counts mask — bit-identical to a fresh
        chunked encode of the unpadded segment)."""
        enc = {}
        for ci, cv in enumerate(self.to_comps(vals)):
            for plane, sym in self.scheme.to_symbols_jnp(cv).items():
                b = self.books[plane]
                enc[(ci, plane)] = recode_chunks_jit(
                    sym.reshape(self.nb, self.eff_chunk), self.counts,
                    jnp.asarray(b.codes), jnp.asarray(b.lengths),
                    max_len=b.max_len)
        return enc

    def decode_hop(self, enc):
        """ppermute the coded words, decode to blocks (selected backend).

        Returns (blocks by (component, plane), component values) — the
        blocks feed the gather-phase recode fast path, the values feed
        the reduce-phase add.
        """
        blocks = {}
        for key, (words, _) in enc.items():
            rw = jax.lax.ppermute(words, self.axis_name, self.perm)
            blocks[key] = decode_blocks(rw, self.counts, self.books[key[1]],
                                        self.eff_chunk, self.decode_backend)
        comps = tuple(
            reassemble({p: blocks[(ci, p)].reshape(-1).astype(jnp.uint8)
                        for p in self.scheme.planes},
                       self.scheme_name, (self.pad_len,), self.dtype)
            for ci in range(self.ncomp))
        return blocks, comps

    def recode(self, blocks):
        """Gather-phase recode: unchanged symbol blocks → coded words."""
        return {key: recode_chunks_jit(
            bl, self.counts, jnp.asarray(self.books[key[1]].codes),
            jnp.asarray(self.books[key[1]].lengths),
            max_len=self.books[key[1]].max_len)
            for key, bl in blocks.items()}

    def reduce_phase(self, start_offset: int, *, encode_final: bool):
        """n−1 fused decode → add → re-encode hops.

        Device i starts with its local copy of segment
        ``(i + start_offset) % n`` and ends owning the fully reduced
        segment ``(i + start_offset + 1) % n``.  Returns
        ``(cur, enc, hop_coded)``: the owned padded segment in the carry
        dtype, its coded form (``None`` when ``encode_final`` is False —
        a standalone reduce-scatter never ships it, all_reduce's first
        gather hop does), and the measured per-hop coded bits.
        """
        hop_coded = []
        cur = self.local_seg(self.i + start_offset)
        enc = self.encode_cur(cur)
        for t in range(self.n - 1):
            hop_coded.append(
                jax.lax.psum(_bits_sum(enc), self.axis_name) / self.n)
            _, comps = self.decode_hop(enc)
            local = self.local_seg(self.i + start_offset - t - 1)
            cur = self.from_comps(comps) + local
            enc = (self.encode_cur(cur)
                   if (t < self.n - 2 or encode_final) else None)
        return cur, enc, hop_coded

    def header_bits(self, hops: int) -> jnp.ndarray:
        return jnp.float32(
            32.0 * self.nb * len(self.scheme.planes) * self.ncomp * hops)

    def raw_seg_bits(self) -> jnp.ndarray:
        return jnp.float32(
            self.seg_len * self.scheme.total_symbol_bits() * self.ncomp)


def ring_reduce_scatter(x, axis_name: str, books: Dict[str, Codebook],
                        scheme_name: str = "bf16", *,
                        chunk: int = DEFAULT_CHUNK,
                        decode_backend: str = DEFAULT_RING_BACKEND,
                        carry: str = "wire"
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Ring reduce-scatter: the all_reduce's first phase, stopped before
    the gather phase — n−1 fused decode→add→re-encode hops.

    The local tensor flattens into n segments of ``ceil(size/n)``
    elements (tail zero-padded when indivisible).  Device i returns the
    **fully reduced segment i** — the flat slice
    ``[i*seg_len : (i+1)*seg_len]`` of the global sum, matching
    ``jax.lax.psum_scatter(..., tiled=True)`` on the flattened tensor.
    Unlike the all_reduce, the final partial sum is never re-encoded:
    the last hop's decode→add ends the op, so exactly n−1 coded
    transfers ride the wire and the analytic volume is the ring
    reduce-scatter minimum (n−1)/n × payload per device.

    ``carry`` selects the hop accumulation dtype exactly as in
    ``ring_all_reduce`` (``"f32"`` ships two wire-dtype components per
    hop at 2× hop payload).  ``hop_coded_bits`` records measured coded
    bits per hop — partial sums compress differently from the inputs
    under the fixed codebook, which is the number a link-level roofline
    needs.
    """
    r = _SegmentRing(x, axis_name, books, scheme_name, chunk,
                     decode_backend, carry)
    payload_coded = jax.lax.psum(
        _coded_payload_bits(x, books, scheme_name), axis_name)
    # start offset −1: device i ends owning segment (i − 1 + 1) % n = i.
    cur, _, hop_coded = r.reduce_phase(-1, encode_final=False)
    y = cur[:r.seg_len].astype(x.dtype)

    coded_wire = sum(hop_coded, jnp.zeros((), jnp.float32))
    stats = {"raw_wire_bits": (r.n - 1) * r.raw_seg_bits(),
             "coded_wire_bits": coded_wire,
             "payload_raw_bits": jnp.float32(
                 r.size * r.scheme.total_symbol_bits()) * r.n,
             "payload_coded_bits": payload_coded,
             "payload_header_bits": r.header_bits(r.n - 1),
             "hop_coded_bits": _stack_hops(hop_coded),
             "hops": jnp.float32(r.n - 1) / r.n}
    return y, stats


def ring_all_reduce(x, axis_name: str, books: Dict[str, Codebook],
                    scheme_name: str = "bf16", *, chunk: int = DEFAULT_CHUNK,
                    decode_backend: str = DEFAULT_RING_BACKEND,
                    carry: str = "wire"
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Ring all-reduce (reduce-scatter + all-gather), coded on every hop.

    The local tensor splits into n segments.  Reduce-scatter phase
    (n−1 hops): each hop ppermutes the coded partial-sum segment, then
    runs the fused hop codec — decode blocks → reassemble on the padded
    block layout → **add** the local contribution → re-extract planes →
    recode blocks — exactly the per-stage pipeline of a hardware ring,
    with no full-stream re-chunking between decode and encode.  The
    final reduce-hop encode *is* the first gather-phase send, so no
    codec pass is wasted.  All-gather phase (n−1 hops): the fully
    reduced segments travel the ring; forwarded symbols are unchanged,
    so each hop recodes the decoder's blocks directly.  Total 2(n−1)
    coded hops; analytic raw volume 2(n−1)/n × payload.

    ``carry`` selects the accumulation dtype across hops: ``"wire"``
    reduces in the scheme dtype (honest link semantics, 1× payload);
    ``"f32"`` keeps float32 partial sums, shipping each hop as two
    wire-dtype components — the rounded value and its residual — for
    training-grade accuracy at exactly 2× hop payload (measured by the
    ledger, pinned in tests).

    ``hop_coded_bits`` records measured coded bits per hop — the
    reduce-scatter hops carry partial sums whose compressibility under
    the fixed codebook genuinely differs from the inputs', which is the
    number a ZipCCL-style deployment needs and an endpoint-decode ledger
    cannot produce.
    """
    r = _SegmentRing(x, axis_name, books, scheme_name, chunk,
                     decode_backend, carry)
    n, i = r.n, r.i
    payload_coded = jax.lax.psum(
        _coded_payload_bits(x, books, scheme_name), axis_name)

    # --- reduce-scatter: n−1 fused decode → add → re-encode hops -------
    cur, enc, hop_coded = r.reduce_phase(0, encode_final=True)

    # device i now owns the fully-reduced segment (i+1)%n; `enc` already
    # holds its coded form — the first gather hop ships it as-is.
    own = (i + 1) % n
    out = jnp.zeros((n, r.seg_len), r.acc_dtype).at[own].set(
        cur[:r.seg_len])

    # --- all-gather: n−1 hops, blocks recode directly (fast path) ------
    for t in range(n - 1):
        hop_coded.append(jax.lax.psum(_bits_sum(enc), axis_name) / n)
        blocks, comps = r.decode_hop(enc)
        out = out.at[(i - t) % n].set(r.from_comps(comps)[:r.seg_len])
        if t < n - 2:                      # last hop's recode never ships
            enc = r.recode(blocks)

    y = out.reshape(-1)[:r.size].reshape(x.shape).astype(x.dtype)

    coded_wire = sum(hop_coded, jnp.zeros((), jnp.float32))
    stats = {"raw_wire_bits": 2.0 * (n - 1) * r.raw_seg_bits(),
             "coded_wire_bits": coded_wire,
             "payload_raw_bits": jnp.float32(
                 r.size * r.scheme.total_symbol_bits()) * n,
             "payload_coded_bits": payload_coded,
             "payload_header_bits": r.header_bits(2 * (n - 1)),
             "hop_coded_bits": _stack_hops(hop_coded),
             "hops": jnp.float32(2 * (n - 1)) / n}
    return y, stats


def ring_all_to_all(x, axis_name: str, books: Dict[str, Codebook],
                    scheme_name: str = "bf16", *, chunk: int = DEFAULT_CHUNK,
                    decode_backend: str = DEFAULT_RING_BACKEND
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """All-to-all over rotated ppermute rounds, coded on every wire.

    ``x`` must carry the n destination shards on its leading axis (the
    ``split_axis=0`` convention): shard j of device i is destined for
    device j.  Round t ∈ {1, …, n−1} ships the single still-in-transit
    shard destined t devices downstream — Huffman-coded in the chunked
    block layout — through the rotated permutation i → (i+t) % n, and
    decodes the shard arriving from t devices upstream.  Every shard
    therefore leaves its source exactly once: per-device egress is the
    all-to-all analytic minimum (n−1)/n × payload, matching the
    ledger-mode accounting (on a physical ring a rotation by t relays
    through t links; ``hop_coded_bits[t−1]`` records the measured coded
    bits of round t so a topology-aware roofline can scale each round by
    its distance).

    Values are forwarded unchanged, so the result is bit-exact vs
    ``jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0)`` for any
    input — this is the MoE expert-dispatch wire (`models/moe.py`), the
    die-to-die-shaped traffic the paper's encoder targets.
    """
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] != n:
        raise ValueError(f"ring_all_to_all needs x.shape[0] == axis size "
                         f"({n}), got {x.shape}")
    scheme = SCHEMES[scheme_name]
    rows = x.reshape(n, -1)
    blk = rows.shape[1]
    eff_chunk = max(1, min(chunk, blk))
    counts_np = chunk_counts_for(blk, eff_chunk)
    counts = jnp.asarray(counts_np)
    nb = int(counts_np.shape[0])
    i = jax.lax.axis_index(axis_name)

    payload_coded = jax.lax.psum(
        _coded_payload_bits(x, books, scheme_name), axis_name)

    # the shard for this device never rides the wire
    out = jnp.zeros_like(rows).at[i].set(jnp.take(rows, i, axis=0))
    hop_coded = []
    for t in range(1, n):
        row = jnp.take(rows, (i + t) % n, axis=0)
        enc = encode_planes(row, books, scheme_name, chunk=eff_chunk)
        hop_coded.append(jax.lax.psum(
            sum((e[1].astype(jnp.float32).sum() for e in enc.values()),
                jnp.zeros((), jnp.float32)), axis_name) / n)
        perm_t = [(j, (j + t) % n) for j in range(n)]
        dec_planes = {}
        for plane, (words, _, _) in enc.items():
            rw = jax.lax.ppermute(words, axis_name, perm_t)
            blocks = decode_blocks(rw, counts, books[plane], eff_chunk,
                                   decode_backend)
            dec_planes[plane] = concat_chunks(
                blocks, counts_np).astype(jnp.uint8)
        val = reassemble(dec_planes, scheme_name, (blk,), x.dtype)
        out = out.at[(i - t) % n].set(val)

    y = out.reshape(x.shape)
    raw_local = jnp.float32(x.size * scheme.total_symbol_bits())
    coded_wire = sum(hop_coded, jnp.zeros((), jnp.float32))
    stats = {"raw_wire_bits": raw_local * (n - 1) / n,
             "coded_wire_bits": coded_wire,
             "payload_raw_bits": raw_local * n,
             "payload_coded_bits": payload_coded,
             "payload_header_bits": jnp.float32(
                 32.0 * nb * len(scheme.planes) * (n - 1)),
             "hop_coded_bits": _stack_hops(hop_coded),
             "hops": jnp.float32(n - 1) / n}
    return y, stats
