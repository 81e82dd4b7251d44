"""Serving engine: batched prefill + autoregressive decode, with optional
fixed-codebook compression accounting on the decode-step activations.

`serve_step` is the function the decode dry-run shapes lower: ONE new
token against a populated KV cache.  The engine wraps it for actual
generation (greedy / temperature sampling) in the examples and tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.compression import CompressionSpec, payload_stats
from ..core.codec import get_codec
from ..core.encoder import (DEFAULT_CHUNK, chunk_counts_for, concat_chunks,
                            encode_chunked_jit, pack_path)
from ..models.common import ModelConfig
from ..models.transformer import decode_step, prefill

__all__ = ["ServeConfig", "Engine", "make_serve_step"]


@dataclass(frozen=True)
class ServeConfig:
    max_cache_len: int
    temperature: float = 0.0   # 0 → greedy
    seed: int = 0


def make_serve_step(model_cfg: ModelConfig,
                    comp_spec: Optional[CompressionSpec] = None, *,
                    decode_chunk: Optional[int] = None, tp_degree: int = 1,
                    ep_degree: int = 1):
    """(params, tokens (B,1), caches, pos) → (logits, caches, metrics).

    With a CompressionSpec, the step also reports the coded size of the
    decode activations payload (what a TP all-gather of the token's
    hidden state would ship) and — via the spec's transport — the wire
    bits that gather costs on a ``tp_degree``-way link
    (``act_wire_*_bits``; 0 when tp_degree == 1).  For MoE models served
    expert-parallel, ``ep_degree > 1`` additionally accounts the
    per-token expert-dispatch all_to_all (``moe_wire_raw_bits``: B ×
    top-k × d_model × wire bits, ×2 dispatch+combine, per MoE layer,
    scaled by the (n−1)/n all-to-all ring factor; the *coded* dispatch
    size is measured where the buffers exist — the per-hop ledger of
    ``models.moe.moe_apply_a2a`` / ``comm.ring.ring_all_to_all``).
    In ``bitexact`` mode
    the step additionally runs the full decompression path — chunked
    encode → chunked decode at the spec's chunk size — and accounts it:
    decoded payload bits, chunk count (the streaming granularity a
    receiving peer overlaps), and a decode-mismatch counter that must
    stay 0 (losslessness observed in production, not assumed), and
    ``act_pack_kernel_chunks``, the chunks the bit-pack kernel both
    looked up and packed (``core.encoder.pack_path``: all of them on a
    TPU at chunk 2048, none on the CPU).  The
    decode tables are rebuilt from the spec's canonical length vectors
    at trace time — exactly what a receiving node holds — and the
    decode runs the spec's ``decode_backend`` (scan / pallas /
    multisym), so the verify path exercises the same decoder a
    receiving peer would.
    """
    books = None
    if decode_chunk is None:
        decode_chunk = (comp_spec.chunk if comp_spec is not None
                        else DEFAULT_CHUNK)
    if (comp_spec is not None and comp_spec.enabled
            and comp_spec.mode == "bitexact"):
        # Rebuild the receiver-side books from the spec's canonical
        # lengths through the spec's codec — exactly what a decoding
        # peer holds (the lengths vector is the whole wire contract for
        # either codec; docs/codecs.md).
        codec = get_codec(comp_spec.codec)
        books = {}
        for plane, lens in comp_spec.plane_lengths:
            lv = np.asarray(lens, dtype=np.int32)
            books[plane] = codec.book_from_lengths(
                lv, key=(comp_spec.tensor_kind, comp_spec.scheme_name, plane))

    n_moe = sum(1 for kind in model_cfg.layer_kinds if "moe" in kind)

    def step(params, tokens, caches, pos):
        logits, caches = decode_step(params, tokens, caches, pos, model_cfg)
        z = jnp.zeros((), jnp.float32)
        metrics = {"act_raw_bits": z, "act_coded_bits": z,
                   "act_wire_raw_bits": z, "act_wire_coded_bits": z,
                   "act_decoded_bits": z, "act_decode_chunks": z,
                   "act_decode_mismatch": z, "act_pack_kernel_chunks": z,
                   "moe_wire_raw_bits": z}
        if (comp_spec is not None and comp_spec.enabled
                and ep_degree > 1 and n_moe):
            from ..comm.transport import RING_FACTORS, moe_dispatch_raw_bits
            dispatch_raw = jnp.float32(moe_dispatch_raw_bits(
                tokens.shape[0], model_cfg.experts_per_token,
                model_cfg.d_model, comp_spec.scheme.total_symbol_bits(),
                n_moe))
            metrics["moe_wire_raw_bits"] = jnp.float32(
                RING_FACTORS["all_to_all"](ep_degree)) * dispatch_raw
        if comp_spec is not None and comp_spec.enabled:
            h = logits.astype(jnp.bfloat16)
            s = payload_stats(h, comp_spec, with_hists=True)
            metrics["act_raw_bits"] = s["raw_bits"]
            metrics["act_coded_bits"] = s["coded_bits"]
            # drift probe (repro.lifecycle): per-batch Shannon floor,
            # the coding epoch, and the per-plane histograms a host
            # lifecycle manager observes to refresh books off-path
            metrics["act_shannon_bits"] = s["shannon_bits"]
            metrics["book_epoch"] = jnp.float32(comp_spec.book_epoch)
            for plane in comp_spec.scheme.planes:
                metrics[f"act_hist_{plane}"] = s[f"hist_{plane}"]
            if tp_degree > 1:
                from ..comm.transport import get_transport
                factor = jnp.float32(
                    get_transport(comp_spec.transport)
                    .wire_factor("all_gather", tp_degree))
                metrics["act_wire_raw_bits"] = factor * s["raw_bits"]
                metrics["act_wire_coded_bits"] = factor * s["coded_bits"]
            if books is not None:
                from ..comm.transport import decode_blocks
                planes = comp_spec.scheme.to_symbols_jnp(h)
                for plane, sym in planes.items():
                    b = books[plane]
                    words, bits = encode_chunked_jit(
                        sym, jnp.asarray(b.codes.astype(np.uint32)),
                        jnp.asarray(b.lengths), chunk=decode_chunk)
                    counts = chunk_counts_for(int(sym.shape[0]), decode_chunk)
                    out = decode_blocks(words, jnp.asarray(counts), b,
                                        decode_chunk,
                                        comp_spec.decode_backend)
                    dec = concat_chunks(out, counts)
                    metrics["act_decoded_bits"] += bits.sum().astype(
                        jnp.float32)
                    metrics["act_decode_chunks"] += jnp.float32(len(counts))
                    if pack_path(decode_chunk,
                                 jax.default_backend()) == "kernel":
                        metrics["act_pack_kernel_chunks"] += jnp.float32(
                            len(counts))
                    metrics["act_decode_mismatch"] += (
                        dec != sym.astype(jnp.uint8)).sum().astype(jnp.float32)
        return logits, caches, metrics

    return step


class Engine:
    """Minimal batched-request engine over the pure-function model API.

    With a ``lifecycle`` manager (``repro.lifecycle``), the engine feeds
    every decode step's activation histograms into the manager and —
    every ``refresh_every`` generated tokens — lets it rebuild stale
    books.  An epoch flip re-binds the spec to the new books and swaps
    in a freshly compiled serve step from the manager's epoch-keyed
    compiled-step cache: the recompile is deliberate, amortized over the
    whole epoch, and happens between decode steps, never inside one.
    """

    def __init__(self, params, model_cfg: ModelConfig, serve_cfg: ServeConfig,
                 comp_spec: Optional[CompressionSpec] = None,
                 tp_degree: int = 1, ep_degree: int = 1,
                 lifecycle=None, refresh_every: int = 16,
                 param_store=None, kv_mode: str = "raw"):
        if param_store is not None:
            if params is not None:
                raise ValueError("pass either params or param_store, not "
                                 "both")
            # Decode-on-load: the store stays the HBM source of truth for
            # footprint accounting; the working copy is materialized once.
            params = param_store.materialize_tree()
        self.params = params
        self.param_store = param_store
        self.cfg = model_cfg
        self.serve = serve_cfg
        self.lifecycle = lifecycle
        self.refresh_every = refresh_every
        self._tp = tp_degree
        self._ep = ep_degree
        self._spec = comp_spec
        if lifecycle is not None and comp_spec is None:
            raise ValueError("a lifecycle manager needs a comp_spec naming "
                             "the tensor kind / scheme / wire config")
        if kv_mode not in ("raw", "coded"):
            raise ValueError(f"kv_mode must be 'raw' or 'coded', "
                             f"got {kv_mode!r}")
        self.kv_mode = kv_mode
        self._kv = self._make_kvstore() if kv_mode == "coded" else None
        self._step = self._compile_step()
        self._prefill = jax.jit(
            partial(prefill, cfg=model_cfg, cache_len=serve_cfg.max_cache_len))
        self._key = jax.random.PRNGKey(serve_cfg.seed)

    def _make_kvstore(self):
        """Coded-KV wrapper, with books resolved in preference order:
        the lifecycle manager's current activation books, the spec's
        canonical plane lengths (what a receiving peer rebuilds), or the
        param store's plane books.  Books are pinned per store — an
        epoch flip mid-generate must not re-key segments already coded —
        so ``generate`` builds a fresh store per call."""
        from ..memstore.kvstore import DEFAULT_KV_CHUNK, CodedKVStore
        spec = self._spec
        if self.lifecycle is not None and spec is not None:
            books = self.lifecycle.books(spec.tensor_kind, spec.scheme_name)
        elif spec is not None and spec.enabled and spec.plane_lengths:
            codec = get_codec(spec.codec)
            books = {
                plane: codec.book_from_lengths(
                    np.asarray(lens, dtype=np.int32),
                    key=(spec.tensor_kind, spec.scheme_name, plane))
                for plane, lens in spec.plane_lengths}
        elif self.param_store is not None:
            # No activation books anywhere: let the KV store build its
            # own from the first ingest's K/V histograms (through the
            # param store's codec) — param-plane books fit rope'd
            # activations poorly enough to cost rate.
            return CodedKVStore(codec=self.param_store.codec,
                                chunk=DEFAULT_KV_CHUNK)
        else:
            raise ValueError("kv_mode='coded' needs books: pass a "
                             "comp_spec (or lifecycle) with activation "
                             "books, or a param_store")
        chunk = spec.chunk if spec is not None else DEFAULT_KV_CHUNK
        return CodedKVStore(books, chunk=chunk)

    def _compile_step(self):
        build = lambda _=None: jax.jit(make_serve_step(  # noqa: E731
            self.cfg, self._spec, tp_degree=self._tp, ep_degree=self._ep))
        if self.lifecycle is None:
            return build()
        # The cache name carries every build-changing knob — engine
        # degrees AND the spec's full wire config — so two engines
        # sharing one manager never collide on a compiled step.
        s = self._spec
        name = (f"serve_step/{self.cfg.name}/{s.tensor_kind}"
                f"/tp{self._tp}ep{self._ep}/{s.mode}/{s.scheme_name}"
                f"/{s.transport}/c{s.chunk}/{s.decode_backend}/{s.carry}"
                f"/{s.axes}")
        return self.lifecycle.compiled(name, build)

    def _maybe_refresh(self) -> bool:
        """Let the manager rebuild stale books; swap in the new epoch's
        spec + compiled step.  Returns True on an epoch flip."""
        if self.lifecycle is None:
            return False
        if self.lifecycle.maybe_refresh() is None:
            return False
        self._spec = self.lifecycle.respec(self._spec)
        self._step = self._compile_step()
        return True

    def _sample(self, logits):
        if self.serve.temperature <= 0.0:
            return jnp.argmax(logits[:, -1], axis=-1)[:, None]
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(
            sub, logits[:, -1] / self.serve.temperature, axis=-1)[:, None]

    def generate(self, prompt_tokens: jnp.ndarray, max_new_tokens: int,
                 prefix_embeds: Optional[jnp.ndarray] = None
                 ) -> Tuple[np.ndarray, Dict[str, float]]:
        """prompt_tokens: (B, S) int32 → (B, max_new_tokens) generated ids."""
        batch = {"tokens": prompt_tokens}
        if prefix_embeds is not None:
            batch["prefix_embeds"] = prefix_embeds
        logits, caches = self._prefill(self.params, batch)
        if self._kv is not None:
            # Fresh coded store per request: ingest the prefill slots,
            # then serve every subsequent step from decoded reads so the
            # logits genuinely flow through the encode→decode round trip.
            self._kv = self._make_kvstore()
            self._kv.ingest(caches)
            caches = self._kv.read(caches)
        prompt_len = prompt_tokens.shape[1] + (
            prefix_embeds.shape[1] if prefix_embeds is not None else 0)
        tok = self._sample(logits).astype(jnp.int32)
        out = [tok]
        totals: Dict[str, float] = {}
        for i in range(max_new_tokens - 1):
            pos = jnp.int32(prompt_len + i)
            logits, caches, m = self._step(self.params, tok, caches, pos)
            if self._kv is not None:
                self._kv.ingest(caches)
                caches = self._kv.read(caches)
            # One host sync for the whole step's metrics dict — not one
            # blocking float() per metric per token.
            m = jax.device_get(m)
            for k, v in m.items():
                if getattr(v, "ndim", 0) > 0:          # per-plane histograms
                    if self.lifecycle is not None and k.startswith("act_hist_"):
                        self.lifecycle.observe(
                            (self._spec.tensor_kind, self._spec.scheme_name,
                             k[len("act_hist_"):]), np.asarray(v))
                    continue
                if k == "book_epoch":                  # level, not a count
                    totals[k] = float(v)
                else:
                    totals[k] = totals.get(k, 0.0) + float(v)
            if (self.lifecycle is not None and self.refresh_every > 0
                    and (i + 1) % self.refresh_every == 0):
                if self._maybe_refresh():
                    totals["book_refreshes"] = totals.get(
                        "book_refreshes", 0.0) + 1.0
            tok = self._sample(logits).astype(jnp.int32)
            out.append(tok)
        for k in ("act_raw_bits", "act_coded_bits", "act_shannon_bits",
                  "act_wire_raw_bits", "act_wire_coded_bits",
                  "act_decoded_bits", "act_decode_chunks",
                  "act_decode_mismatch", "act_pack_kernel_chunks",
                  "moe_wire_raw_bits", "book_epoch"):
            totals.setdefault(k, 0.0)                  # stable for 1-token gens
        totals.update(self.hbm_stats())
        return np.concatenate([np.asarray(t) for t in out], axis=1), totals

    def hbm_stats(self) -> Dict[str, float]:
        """Compressed-at-rest HBM ledger (params + KV), reported next to
        the wire ledger in ``generate`` totals.  Zeros when the engine
        holds everything raw; ``hbm_effective_bandwidth_x`` is the
        raw/coded multiplier a memory-bound decode step gains by reading
        coded bytes."""
        stats = {"param_hbm_raw_bits": 0.0, "param_hbm_coded_bits": 0.0,
                 "kv_hbm_raw_bits": 0.0, "kv_hbm_coded_bits": 0.0}
        if self.param_store is not None:
            fp = self.param_store.footprint()
            stats["param_hbm_raw_bits"] = float(fp["hbm_raw_bits"])
            stats["param_hbm_coded_bits"] = float(fp["hbm_coded_bits"])
        if self._kv is not None:
            stats["kv_hbm_raw_bits"] = float(self._kv.kv_hbm_raw_bits)
            stats["kv_hbm_coded_bits"] = float(self._kv.kv_hbm_coded_bits)
        raw = stats["param_hbm_raw_bits"] + stats["kv_hbm_raw_bits"]
        coded = stats["param_hbm_coded_bits"] + stats["kv_hbm_coded_bits"]
        stats["hbm_raw_bits"] = raw
        stats["hbm_coded_bits"] = coded
        stats["hbm_effective_bandwidth_x"] = (raw / coded) if coded else 0.0
        return stats
