"""``correct`` on the CPU at a test's size: sound runs pass, the control
(the reference one precision lower, in the program's place) fails, and
a run with the timed path broken underneath fails, once for each fault
that the cell can have."""
import time

import jax.numpy as jnp
import pytest

from bench.harness import run_cell

SERVE = "serve.tiny-dense.tiny_decode"
SEEDS = (0, 1, 2**40 + 3)


def run(data_bench, seed, control=False):
    return run_cell(data_bench.cell(SERVE), seed=seed, seconds=0,
                    trace=False, t_start=time.perf_counter(),
                    require_chip=False, control=control)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_runs_are_correct(data_bench, no_cache, seed):
    result = run(data_bench, seed)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(data_bench, no_cache, seed):
    result = run(data_bench, seed, control=True)
    assert not result["correct"], result["checks"]
    assert result["checks"]["served_logit_gap"]["value"] > \
        result["checks"]["served_logit_gap"]["limit"]


# ---------------------------------------------------------------- faults
def serve_fault(kind):
    import repro.serve.engine as engine
    make = engine.make_serve_step

    def faulty_make(*args, **kw):
        step = make(*args, **kw)

        def faulty(params, tokens, caches, pos):
            logits, new, metrics = step(params, tokens, caches, pos)
            if kind == "state_unchanged":
                new = caches
            elif kind == "half_batch":
                half = logits.shape[0] // 2
                logits = jnp.concatenate([logits[:half]] * 2)
            elif kind == "token_altered":
                alt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
                bump = jnp.where(pos == pos.dtype.type(20), 10.0, 0.0)
                logits = logits + bump * (
                    jnp.arange(logits.shape[-1]) == alt[..., None])
            elif kind == "decode_dropped":
                metrics = dict(metrics, act_decode_chunks=0 * metrics[
                    "act_decode_chunks"], act_decoded_bits=0 * metrics[
                    "act_decoded_bits"])
            return logits, new, metrics
        return faulty
    return engine, "make_serve_step", faulty_make


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered", "decode_dropped"])
def test_fault_is_not_correct(data_bench, no_cache, monkeypatch, kind):
    monkeypatch.setattr(*serve_fault(kind))
    result = run(data_bench, 5)
    assert not result["correct"], result["checks"]


def test_books_do_not_follow_the_seed(data_bench, no_cache):
    """Every seed serves with the same books, so the decode step that
    bakes their code lengths in compiles once for all seeds."""
    from bench.harness import devices_for, generator_class
    cell = data_bench.cell(SERVE)
    specs = []
    for seed in (7, 2**40 + 7):
        g = generator_class(cell)(cell, seed, devices_for(cell, False))
        g.setup()
        specs.append(g.engine._spec)
    assert specs[0] == specs[1]
