"""Quad Length Codes (QLC) — the four-length prefix code fast path.

A QLC codebook restricts the code to exactly four lengths
``l0 ≤ l1 ≤ l2 ≤ l3`` (each in ``[2, 16]``): every codeword is a 2-bit
**class** prefix ``c`` followed by ``l_c − 2`` index bits, so the code is
prefix-free by construction (the prefixes partition the code space into
four quarters and each class spends at most its quarter:
``2^(l_c−2) · 2^−l_c = 1/4``) and the decoder reads the code length from
the two leading window bits — no canonical-prefix subtraction, no
per-window LUT, just shifts and one 256-entry symbol gather.  That is
the whole trade the follow-up paper makes: a sliver of ratio (the PMF is
quantized onto four quantile buckets instead of per-symbol lengths) for
a branchless, table-free hot loop — exactly what the ring hop codec
wants, where every payload is re-coded 2(n−1) times per all_reduce.

Construction ("length assignment by PMF quantile"): symbols are sorted
by probability and the four classes are filled greedily in order — the
``2^(l0−2)`` most probable symbols get length ``l0``, the next
``2^(l1−2)`` get ``l1``, and so on.  For a fixed length tuple this
greedy quantile fill is optimal (capacities and lengths both grow with
the class index), so the builder simply scores **every** feasible
non-decreasing 4-tuple over ``[2, max_len]`` (≤ 3060 candidates — one
(T, n) · (n,) matvec) and keeps the argmin expected bits.  Equal lengths
across classes are allowed: ``(8, 8, 8, 8)`` is the uniform-256 code
(2 prefix + 6 index bits = the identity byte code).

Canonical rule: within a class, member symbols are ordered by symbol
value, so the full code assignment is a **pure function of the
per-symbol lengths vector** — ``qlc_book_from_lengths`` rebuilds the
identical book from a ``CompressionSpec``'s lengths, mirroring what
canonical ordering does for Huffman books (see ``serve.engine``).

Wire format: identical to Huffman — codes ride the shared
``_encode_rows`` lookup-and-pack core (MSB-first, 32-bit words) and
``max_len`` stays ``MAX_CODE_LEN`` so ``chunk_capacity_words`` and the
chunked-stream capacity are byte-compatible across codecs; only the
(codes, lengths) LUT and the decoder differ.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .entropy import compressibility, expected_code_length
from .huffman import MAX_CODE_LEN

__all__ = [
    "QLC_CLASSES", "QLC_PREFIX_BITS", "QLC_MIN_LEN",
    "QLCBook", "build_qlc_book", "qlc_book_from_lengths",
    "qlc_decode_args", "qlc_kernel_args", "decode_chunks_qlc_jit",
]

QLC_CLASSES = 4        # fixed by the 2-bit prefix
QLC_PREFIX_BITS = 2
QLC_MIN_LEN = 2        # prefix-only code (class capacity 1)

# The decoder reads a 16-bit window and takes the class from its top two
# bits, so no class length may exceed 16 even if the wire capacity
# (max_len) were ever raised.
_QLC_WINDOW_BITS = 16


def _class_capacity(length: int) -> int:
    return 1 << (length - QLC_PREFIX_BITS)


@lru_cache(maxsize=8)
def _candidate_tables(n: int, max_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """All feasible non-decreasing length 4-tuples for an n-symbol
    alphabet, plus the (T, n) rank → length matrix the builder scores.

    Feasible = the four class capacities cover all n symbols.  Tuples
    are enumerated in lexicographic order so the argmin tie-break is
    deterministic across hosts (fleet-critical: every replica must
    build the identical book from the identical histogram).
    """
    from itertools import combinations_with_replacement
    hi = min(max_len, _QLC_WINDOW_BITS)
    tuples = []
    rows = []
    for t in combinations_with_replacement(range(QLC_MIN_LEN, hi + 1),
                                           QLC_CLASSES):
        caps = [_class_capacity(l) for l in t]
        if sum(caps) < n:
            continue
        tuples.append(t)
        rows.append(np.repeat(np.asarray(t, np.int16), caps)[:n])
    if not tuples:
        raise ValueError(f"no feasible QLC length tuple for n={n} "
                         f"with max_len={max_len}")
    return np.asarray(tuples, np.int32), np.stack(rows)


@dataclass(frozen=True)
class QLCBook:
    """A fixed four-length (QLC) codebook over an n-symbol alphabet.

    Duck-types the host-side surface of ``codebook.Codebook`` (lengths,
    codes, encoded_bits, code_lut, …) so the encoder, the registry, the
    drift monitor and the wire accounting are codec-agnostic; only the
    decode tables differ — four packed scalars plus a dense (n,)
    pointer → symbol table instead of the canonical-prefix walk.
    """
    book_id: int
    key: Tuple[str, str, str]
    lengths: np.ndarray            # (n,) int32 per-symbol code length
    codes: np.ndarray              # (n,) uint32, MSB-first, right-aligned
    class_lengths: Tuple[int, int, int, int]   # l0 ≤ l1 ≤ l2 ≤ l3
    class_bases: Tuple[int, int, int, int]     # symbols in classes < c
    sym_tab: np.ndarray            # (n,) int32: dense pointer → symbol
    source_counts: np.ndarray      # the (smoothed) histogram it came from
    max_len: int = MAX_CODE_LEN    # wire-capacity bound (chunk_capacity_words)
    # Lazily-built 2^16 window → symbol LUT for the scan decoder's
    # parallel emission phase; a mutable cache is fine inside the frozen
    # dataclass — the book itself never changes (same pattern as
    # ``Codebook._multisym_cache``).
    _lut_cache: Dict[str, np.ndarray] = field(
        default_factory=dict, repr=False, compare=False)

    codec_name = "qlc"

    def expected_bits_per_symbol(self, counts: np.ndarray) -> float:
        return float(expected_code_length(counts, self.lengths))

    def encoded_bits(self, counts: np.ndarray) -> int:
        """Exact payload size in bits for a message with this histogram."""
        return int(np.dot(np.asarray(counts, np.int64),
                          self.lengths.astype(np.int64)))

    def compressibility(self, counts: np.ndarray, symbol_bits: int = 8) -> float:
        return float(compressibility(self.expected_bits_per_symbol(counts),
                                     symbol_bits))

    def code_lut(self) -> np.ndarray:
        """(n, 2) uint32 [code, length] table — the encoder kernel's LUT."""
        return np.stack([self.codes.astype(np.uint32),
                         self.lengths.astype(np.uint32)], axis=1)

    # ------------------------------------------------------ decode scalars
    def len_pack(self) -> int:
        """Four class lengths packed 8 bits apiece into one uint32 —
        the decoder's length "table" is two scalar shifts."""
        l0, l1, l2, l3 = self.class_lengths
        return l0 | (l1 << 8) | (l2 << 16) | (l3 << 24)

    def base_pack(self) -> int:
        """Class bases 1..3 packed 10 bits apiece (base 0 is always 0;
        a base can reach n=256, which needs the tenth bit)."""
        _, b1, b2, b3 = self.class_bases
        return b1 | (b2 << 10) | (b3 << 20)

    def window_lut(self) -> np.ndarray:
        """(2^16,) int32 window → symbol table for the scan decoder's
        parallel phase-2 emission (cached).

        Pure denormalization of ``sym_tab`` over every 16-bit window:
        the serial phase stays table-free (class/length from the two
        leading bits), and resolving the decoded window to a symbol
        becomes one parallel gather per output slot instead of per-step
        base/pointer arithmetic inside the scan.  Windows whose class
        slot is unoccupied (they cannot occur in a valid stream) map to
        0.
        """
        if "win" not in self._lut_cache:
            w = np.arange(1 << _QLC_WINDOW_BITS, dtype=np.uint32)
            cl = np.asarray(self.class_lengths, np.uint32)
            cb = np.asarray(self.class_bases, np.int64)
            c = (w >> (_QLC_WINDOW_BITS - QLC_PREFIX_BITS)).astype(np.int64)
            l = cl[c]
            idx = ((w >> (_QLC_WINDOW_BITS - l))
                   & ((np.uint32(1) << (l - QLC_PREFIX_BITS)) - 1))
            ptr = cb[c] + idx.astype(np.int64)
            n = self.sym_tab.shape[0]
            self._lut_cache["win"] = np.where(
                ptr < n, self.sym_tab[np.minimum(ptr, n - 1)], 0
            ).astype(np.int32)
        return self._lut_cache["win"]


def qlc_book_from_lengths(lengths: np.ndarray, *, book_id: int = -1,
                          key: Tuple[str, str, str] = ("", "", ""),
                          source_counts: Optional[np.ndarray] = None,
                          max_len: int = MAX_CODE_LEN) -> QLCBook:
    """Rebuild the canonical QLC book from its per-symbol lengths vector.

    The class structure is recovered from the lengths alone: each
    distinct length L present needs ``ceil(n_L / 2^(L−2))`` classes, in
    ascending length order; within a class, symbols are ordered by
    value.  More than four classes — or any length outside
    ``[2, min(max_len, 16)]`` — means the vector is not a QLC code.
    """
    lengths = np.asarray(lengths, dtype=np.int32)
    n = lengths.shape[0]
    hi = min(max_len, _QLC_WINDOW_BITS)
    lo, top = int(lengths.min()), int(lengths.max())
    if lo < QLC_MIN_LEN or top > hi:
        # The longest class length is also what chunk_capacity_words
        # sizes the wire for (via max_len) — a length past the bound
        # would overflow the chunk word capacity, not just the window.
        raise ValueError(
            f"QLC code lengths must lie in [{QLC_MIN_LEN}, {hi}] "
            f"(2-bit prefix, 16-bit decode window, chunk capacity sized "
            f"for max_len={max_len}); got [{lo}, {top}]")
    classes = []
    for L in sorted(set(int(v) for v in lengths)):
        n_L = int((lengths == L).sum())
        cap = _class_capacity(L)
        classes.extend([L] * (-(-n_L // cap)))
    if len(classes) > QLC_CLASSES:
        raise ValueError(f"lengths need {len(classes)} classes; QLC has "
                         f"exactly {QLC_CLASSES} (2-bit prefix)")
    classes.extend([hi] * (QLC_CLASSES - len(classes)))   # unused classes

    codes = np.zeros(n, dtype=np.uint32)
    sym_tab = np.zeros(n, dtype=np.int32)
    bases = []
    ptr = 0
    remaining: Dict[int, list] = {}
    for s in range(n):                     # symbol-value order per length
        remaining.setdefault(int(lengths[s]), []).append(s)
    for c, L in enumerate(classes):
        bases.append(ptr)
        members = remaining.get(L, [])
        take = members[:_class_capacity(L)]
        remaining[L] = members[len(take):]
        for i, s in enumerate(take):
            codes[s] = np.uint32((c << (L - QLC_PREFIX_BITS)) | i)
            sym_tab[ptr + i] = s
        ptr += len(take)
    if ptr != n:
        raise ValueError("QLC class capacities do not cover the lengths "
                         "vector — not a canonical QLC code")
    if source_counts is None:
        source_counts = np.zeros(n, dtype=np.int64)
    return QLCBook(book_id=book_id, key=key, lengths=lengths, codes=codes,
                   class_lengths=tuple(classes), class_bases=tuple(bases),
                   sym_tab=sym_tab,
                   source_counts=np.asarray(source_counts),
                   max_len=max_len)


def build_qlc_book(counts: np.ndarray, *, book_id: int = -1,
                   key: Tuple[str, str, str] = ("", "", ""),
                   max_len: int = MAX_CODE_LEN, floor: int = 1,
                   n_symbols: Optional[int] = None) -> QLCBook:
    """Build the expected-bits-optimal QLC book from a probe histogram.

    Same contract as ``codebook.build_codebook``: ``floor`` smoothing
    makes the code total, the build is deterministic (stable sort,
    lexicographic tuple tie-break), and the result is canonical — it
    round-trips through ``qlc_book_from_lengths(book.lengths)``.
    """
    counts = np.asarray(counts, dtype=np.int64)
    n = counts.shape[0]
    if n_symbols is not None and n != n_symbols:
        raise ValueError(f"histogram has {n} bins, expected {n_symbols}")
    smoothed = np.maximum(counts, floor)
    tuples, rank_len = _candidate_tables(n, max_len)
    order = np.lexsort((np.arange(n), -smoothed))   # prob desc, value asc
    costs = rank_len.astype(np.float64) @ smoothed[order].astype(np.float64)
    best = int(np.argmin(costs))
    lengths = np.empty(n, dtype=np.int32)
    lengths[order] = rank_len[best].astype(np.int32)
    # Canonicalize through the lengths vector (drops the scorer's choice
    # of unused trailing classes) so build and from_lengths agree bit
    # for bit on every replica.
    return qlc_book_from_lengths(lengths, book_id=book_id, key=key,
                                 source_counts=smoothed, max_len=max_len)


def qlc_decode_args(book: QLCBook) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device arrays for the XLA scan decoder: the packed class-length
    scalar and the 2^16 window → symbol emission LUT."""
    return (jnp.uint32(book.len_pack()),
            jnp.asarray(book.window_lut(), jnp.int32))


def qlc_kernel_args(book: QLCBook) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """Device arrays for the Pallas kernel: both packed scalars plus the
    dense (n,) pointer → symbol table (the kernel resolves pointers
    inline per symbol, keeping its VMEM footprint at n entries instead
    of the scan decoder's 2^16 emission LUT)."""
    return (jnp.uint32(book.len_pack()), jnp.uint32(book.base_pack()),
            jnp.asarray(book.sym_tab, jnp.int32))


# --------------------------------------------------------------------------
# Branchless chunked decode (XLA lax.scan formulation)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("chunk", "max_len"))
def decode_chunks_qlc_jit(block_words: jnp.ndarray, chunk_counts: jnp.ndarray,
                          len_pack: jnp.ndarray, window_lut: jnp.ndarray,
                          chunk: int,
                          max_len: int = MAX_CODE_LEN) -> jnp.ndarray:
    """Chunked QLC decode: one gather plus a handful of ALU ops per symbol.

    Phase 1 is a ``lax.scan`` over output slots (all chunks in
    lockstep), but — unlike the Huffman walks — the body holds **no
    decode tables**: the 16-bit window's top two bits select the class
    and the class length comes out of one packed scalar by shift, so
    the only memory op per step is the half-word window fetch (the same
    H-array trick as the multisym decoder), versus the multisym walk's
    window fetch *plus* step-table gather.  The body emits the raw
    window; masking, pointer math and symbol resolution all move to
    phase 2, one parallel ``window_lut[win]`` gather per output slot.
    (Decoding past a chunk's true bit count is harmless — the capacity
    pad is zeros, the window fetch is clamped in-bounds, and phase 2
    masks dead slots by count — so the scan body carries no liveness
    selects at all.)  That halved-and-slimmed serial step is where the
    measured ~2–3.5× over multisym on e4m3 payloads comes from.

    block_words (NB, cap) uint32, chunk_counts (NB,) int32,
    len_pack () uint32 (``QLCBook.len_pack``), window_lut (2^16,) int32
    (``QLCBook.window_lut``) → (NB, chunk) int32 symbols, zero-filled
    past each chunk's count.  Bit-exact vs ``kernels.ref.decode_qlc_np``.
    """
    nb, cap = block_words.shape
    words = block_words.astype(jnp.uint32)
    counts = chunk_counts.astype(jnp.int32)
    lut = window_lut.astype(jnp.int32)
    lp = len_pack.astype(jnp.uint32)

    # Half-word window array: H[q] holds stream bits [16q, 16q+32), so
    # any 16-bit window is one gather plus two shifts.  Flattened with
    # per-chunk offsets — measurably faster than take_along_axis here.
    nxt = jnp.concatenate([words[:, 1:], jnp.zeros((nb, 1), jnp.uint32)],
                          axis=1)
    Hf = jnp.stack([words, (words << 16) | (nxt >> 16)],
                   axis=2).reshape(-1)
    offs = jnp.arange(nb, dtype=jnp.int32) * (2 * cap)

    def body(bit_pos, _):
        q = jnp.minimum((bit_pos >> jnp.uint32(4)).astype(jnp.int32),
                        2 * cap - 1)
        h = Hf[q + offs]
        win = (h << (bit_pos & jnp.uint32(15))) >> jnp.uint32(16)
        c = win >> jnp.uint32(14)                            # 2-bit class
        l = (lp >> (c << jnp.uint32(3))) & jnp.uint32(0xFF)
        return bit_pos + l, win

    # Cursor derives from `words` (0-valued) so its varying-axes type
    # matches the body output under shard_map (same trick as the
    # canonical and multisym scans).  unroll=2 measured best among
    # {1, 2, 4, 8, 16} on XLA:CPU.
    cursor0 = (words[:, 0] & jnp.uint32(0))
    _, wins = jax.lax.scan(body, cursor0, None, length=chunk,
                           unroll=min(2, chunk))

    # ---- phase 2: one gather per output slot.  wins (chunk, NB).
    out = lut[wins.T.astype(jnp.int32)]
    o = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    return jnp.where(o < counts[:, None], out, 0)
