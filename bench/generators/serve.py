"""Serve traffic: closed-loop static batches through ``Engine.generate``.

The traffic file gives the batch, the prompt and output lengths, the
cache length and the compression; the configuration gives the model.
Set-up makes the weights and a pool of prompts on the device from the
seed, builds fixed Huffman books from the traffic file's draw (which
does not follow the seed, so that every seed runs the same compiled
decode step), and warms the engine's programs with a two-token batch of
the same shapes.  The window then serves one batch after another until
``--seconds`` have passed, and ends with the last batch.

The engine syncs each step's metrics to the host before it dispatches
the next step, so wrapping its prefill and step callables with a
``block_until_ready`` changes nothing it waits for, and stamps the time
each token becomes available: the prefill's for the first token, step
k's for token k + 1.

``check`` compares what the window served with the plain reference
named by the configuration (float32, whole sequences): a sample of the
finished requests drawn from the seed, every served token's reference
logit against the reference's best at its position.  The engine's own
in-graph counter of decoded activation symbols that differ from the
encoded ones covers the coded round trip; its counts of raw bits and
decoded chunks are held against the traffic's own shapes, so a step
that codes or decodes less than the whole of its logits shows.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.harness import (BenchError, check, log, reference_module,
                           seed_key)


def model_config(c: dict, registry: str | None = None):
    """The program's ``ModelConfig`` for a dense GQA configuration file.

    Where the file names a registry entry, every width this cell runs
    has to agree with it; the entry is otherwise not used.
    """
    from repro.models import BlockGroup, ModelConfig
    head_dim = c.get("head_dim") or c["hidden_size"] // c[
        "num_attention_heads"]
    cfg = ModelConfig(
        name=c["name"], arch_type="dense", d_model=c["hidden_size"],
        vocab_size=c["vocab_size"],
        blocks=(BlockGroup(("attn",), c["num_hidden_layers"]),),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=head_dim,
        d_ff=c["intermediate_size"], qk_norm=c.get("model_type") == "qwen3",
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        ffn_activation=c["hidden_act"], dtype=jnp.bfloat16)
    if registry:
        from repro.configs import get_config
        reg = get_config(registry)
        keys = ("d_model", "vocab_size", "blocks", "n_heads", "n_kv_heads",
                "head_dim", "d_ff", "qk_norm", "rope_theta", "norm_eps",
                "tie_embeddings", "ffn_activation", "dtype")
        diff = {k: (getattr(reg, k), getattr(cfg, k)) for k in keys
                if getattr(reg, k) != getattr(cfg, k)}
        if diff:
            raise BenchError(f"registry entry {registry!r} departs from "
                             f"the configuration file: {diff}")
    return cfg


def fixed_books(t: dict, c: dict) -> dict:
    """Huffman books, one a byte plane, from the traffic file's fixed
    draw: ``batch`` rows of ``vocab_size`` normal values at the scale of
    the served logits, in bf16.  The books are complete (every symbol
    has a code), so any logits code losslessly."""
    from repro.core.codec import get_codec
    from repro.core.symbols import SCHEMES
    b = t["books"]
    x = b["std"] * jax.random.normal(jax.random.PRNGKey(b["key"]),
                                     (t["batch"], c["vocab_size"]))
    planes = SCHEMES["bf16"].to_symbols_jnp(x.astype(jnp.bfloat16))
    codec = get_codec(t["compression"]["codec"])
    return {p: codec.build_book(
        np.asarray(jnp.bincount(s.astype(jnp.int32), length=256)),
        key=("bench", p)) for p, s in planes.items()}


def layout(tree) -> list:
    return [(jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
            for p, a in jax.tree.flatten_with_path(tree)[0]]


class Generator:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.c, self.t = cell.config, cell.traffic
        self.ref = reference_module(self.c)
        self.annotate = lambda _: contextlib.nullcontext()
        self.marks: list = []
        self.batches: list = []

    # ----------------------------------------------------------- set-up
    def setup(self):
        from repro.comm.compression import CompressionSpec
        from repro.models import model_init
        from repro.serve.engine import Engine, ServeConfig

        t, c = self.t, self.c
        self.mcfg = model_config(c, c.get("registry"))
        k_params, k_prompts = jax.random.split(seed_key(self.seed))
        self.params = self.ref.make_params(c, k_params)
        want = jax.eval_shape(lambda k: model_init(self.mcfg, k),
                              jax.random.PRNGKey(0))
        if layout(want) != layout(self.params):
            raise BenchError("the program's parameter layout differs from "
                             "the benchmark's")
        shape = (t["prompt_pool"], t["batch"], t["prompt_len"])
        pool = jax.random.randint(k_prompts, shape, 0, c["vocab_size"],
                                  jnp.int32)
        self.prompts = list(pool)          # sliced here, not in the window
        serve = ServeConfig(max_cache_len=t["max_cache_len"])
        books = fixed_books(t, c)
        self.planes = len(books)
        comp = t["compression"]
        spec = CompressionSpec.from_books(books, "bf16",
                                          tensor_kind=comp["tensor_kind"],
                                          mode=comp["mode"],
                                          chunk=comp["chunk"])
        self.engine = Engine(self.params, self.mcfg, serve, comp_spec=spec)
        self._instrument()
        self._batch(0, 2)                  # every program, same shapes
        self.marks, self.batches = [], []

    def _instrument(self):
        e = self.engine
        prefill, step, sample = e._prefill, e._step, e._sample

        def stamped(fn, label):
            def call(*args):
                with self.annotate(label):
                    out = jax.block_until_ready(fn(*args))
                self.marks.append(time.perf_counter())
                return out
            return call

        def sampled(logits):
            with self.annotate("sample"):
                return sample(logits)

        e._prefill, e._step = stamped(prefill, "prefill"), stamped(step,
                                                                   "step")
        e._sample = sampled

    # ----------------------------------------------------------- window
    def _batch(self, index: int, new_tokens: int):
        self.marks = []
        with self.annotate("batch"):
            toks, totals = self.engine.generate(
                self.prompts[index % len(self.prompts)], new_tokens)
        self.batches.append({"prompt": index % len(self.prompts),
                             "tokens": toks,
                             "mismatch": totals["act_decode_mismatch"],
                             "coded_bits": totals["act_coded_bits"],
                             "raw_bits": totals["act_raw_bits"],
                             "chunks": totals["act_decode_chunks"],
                             "gaps": np.diff(self.marks).tolist()})

    def _record(self, t0: float) -> dict:
        window_s = time.perf_counter() - t0
        n = len(self.batches)
        t = self.t
        return {"window_s": window_s, "attempted": n * t["batch"],
                "failed": 0, "sequences": n * t["batch"],
                "output_tokens": n * t["batch"] * t["new_tokens"],
                "gaps_s": [g for b in self.batches for g in b["gaps"]],
                "flops_per_sequence": flops.output_token_flops(
                    self.c, t["prompt_len"], t["new_tokens"]),
                "act_coded_bits": sum(b["coded_bits"] for b in self.batches),
                "act_raw_bits": sum(b["raw_bits"] for b in self.batches)}

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while not self.batches or time.perf_counter() - t0 < seconds:
            self._batch(len(self.batches), self.t["new_tokens"])
        rec = self._record(t0)
        gaps = sorted(rec["gaps_s"])
        log(f"window {rec['window_s']:.3f}s: {len(self.batches)} batches, "
            f"token gaps {sum(gaps):.3f}s in all, longest "
            f"{[round(g, 4) for g in gaps[-3:]]}s; the rest is prefills "
            f"and host work between batches")
        return rec

    def traced_window(self, annotate) -> None:
        """One more batch, cut to its prefill and first ``trace_steps``
        decode steps (a whole batch is millions of device events, more
        than the profiler keeps), with the harness's spans around each
        call.  It is run after the window, with the same programs, and
        is neither timed nor checked."""
        self.annotate = annotate
        self._batch(0, self.t["trace_steps"] + 1)
        self.batches.pop()
        self.annotate = lambda _: contextlib.nullcontext()

    def trace_modules(self) -> dict:
        return self.t["programs"]

    # ------------------------------------------------------------ check
    def served_sample(self):
        """(prompts, served tokens) of the requests the check reads."""
        rng = np.random.default_rng(self.seed)
        done = [(b, r) for b in range(len(self.batches))
                for r in range(self.t["batch"])]
        pick = rng.choice(len(done), min(self.t["check_requests"],
                                         len(done)), replace=False)
        prompts = np.stack([np.asarray(
            self.prompts[self.batches[done[i][0]]["prompt"]])[done[i][1]]
            for i in pick])
        served = np.stack([self.batches[done[i][0]]["tokens"][done[i][1]]
                           for i in pick])
        return prompts, served

    def reference_logits(self, prompts, served, *, low: bool = False):
        """The reference's logits at each served token's position."""
        seq = jnp.asarray(np.concatenate([prompts, served[:, :-1]], 1))
        return self.ref.logits(self.c, self.params, seq,
                               prompts.shape[1] - 1, low=low)

    def check(self, control: bool = False) -> list:
        """The numbers ``correct`` compares, each beside its limit.  With
        ``control`` the reference one precision lower (float8) stands in
        the program's place: the served tokens it reads are the ones the
        float8 reference puts first at each position."""
        lim = self.cell.limits
        t, c = self.t, self.c
        total = {k: sum(b[k] for b in self.batches)
                 for k in ("mismatch", "raw_bits", "chunks")}
        steps = len(self.batches) * (t["new_tokens"] - 1)
        values = t["batch"] * c["vocab_size"]          # logits a step
        chunk = t["compression"]["chunk"]
        want_raw = steps * values * 8 * np.dtype(jnp.bfloat16).itemsize
        want_chunks = steps * self.planes * -(-values // chunk)
        prompts, served = self.served_sample()
        self.engine = None                     # the program's state goes
        ref = self.reference_logits(prompts, served)
        if control:
            low = self.reference_logits(prompts, served, low=True)
            served = jnp.argmax(low, axis=-1)
            del low
        gap = float(self.ref.served_gap(ref, jnp.asarray(served)))
        return [check("act_decode_mismatch", total["mismatch"],
                      lim["act_decode_mismatch"]["limit"]),
                check("act_raw_bits_error", abs(total["raw_bits"] - want_raw),
                      lim["act_raw_bits_error"]["limit"]),
                check("act_decode_chunks_error",
                      abs(total["chunks"] - want_chunks),
                      lim["act_decode_chunks_error"]["limit"]),
                check("served_logit_gap", gap,
                      lim["served_logit_gap"]["limit"])]
