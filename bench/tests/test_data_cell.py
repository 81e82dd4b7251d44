"""A cell defined only by files under ``testdata/cell`` is found,
validated and run by the harness on the CPU, with no edit to harness
code; the measuring entry still refuses to run without a TPU."""
import json
import shutil
import subprocess
import sys
import time

import pytest

from bench.harness import (BENCH, CHECKOUT, BenchError, Benchmark, NoChip,
                           devices_for, run_cell, seed_key)

DATA = BENCH / "testdata" / "cell"
SERVE = "serve.tiny-dense.tiny_decode"


def test_loader_finds_the_data_cell(data_bench):
    assert SERVE in data_bench.names()
    cell = data_bench.cell(SERVE)
    assert cell.traffic["generator"] == "serve"
    assert cell.config["hidden_size"] == 256
    assert [m["name"] for m in cell.metrics[0]] == [
        "output_tok_s", "tpot_p95_ms", "setup_s"]
    assert "decode_mfu" in [m["name"] for m in cell.metrics[1]]
    with pytest.raises(BenchError):
        data_bench.cell("serve.tiny-dense.absent")


def test_every_benchmark_cell_loads():
    bench = Benchmark()
    for name in bench.names():
        cell = bench.cell(name)
        assert cell.metrics[0] and cell.metrics[1]


def test_missing_part_is_refused(tmp_path):
    shutil.copytree(DATA, tmp_path / "cell")
    (tmp_path / "cell" / "traffic" / "tiny_decode.json").unlink()
    bench = Benchmark(tmp_path / "cell" / "BENCHMARK.json", tmp_path / "cell")
    with pytest.raises(BenchError, match="tiny_decode"):
        bench.cell(SERVE)


def test_data_cell_runs_on_the_cpu(data_bench, no_cache):
    cell = data_bench.cell(SERVE)
    result = run_cell(cell, seed=2**40 + 1, seconds=0.5, trace=False,
                      t_start=time.perf_counter(), require_chip=False)
    assert result["correct"]
    assert set(result["metrics"]) == {"output_tok_s", "tpot_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    assert result["attempted"] % 4 == 0 and result["failed"] == 0
    with pytest.raises(NoChip):
        devices_for(cell)                      # the chip check still holds


def test_traced_run_reads_the_window(data_bench, no_cache, monkeypatch):
    """A traced run measures the same window as an untraced one and
    traces a short stretch after it.  The CPU's trace has no device
    plane, so a stand-in takes the reduction's place here, and a
    made-up peak stands for the CPU's."""
    import bench.peaks
    import bench.trace as trace_mod
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu", {"bf16_flop_s": 1e12})
    stub = {"busy_s": 0.5, "window_s": 1.0, "idle_share": 0.5,
            "top_ops": [["%while", 0.25]], "idle_gaps": [["bench.step", 0.1]]}
    seen = []
    monkeypatch.setattr(trace_mod, "reduce_dir",
                        lambda where, **kw: seen.append(kw) or stub)
    cell = data_bench.cell(SERVE)
    result = run_cell(cell, seed=2**40 + 2, seconds=0.5, trace=True,
                      t_start=time.perf_counter(), require_chip=False)
    assert result["correct"], result["checks"]
    assert seen and seen[0]["window"] == "bench.window"
    metrics = result["metrics"]
    assert set(metrics) == {"decode_mfu", "idle_share.serve"}
    assert metrics["idle_share.serve"]["value"] == 50.0
    assert 0 < metrics["decode_mfu"]["value"]
    assert result["device"]["busy_s"] == 0.5
    assert result["breakdown"]["device_ops"] == [["%while", 0.25]]
    assert result["attempted"] % 4 == 0


def test_seed_keeps_all_its_bits():
    import jax
    a, b = (jax.random.key_data(seed_key(s)) for s in (3, 2**40 + 3))
    assert (a != b).any()


def run_entry(cwd, *args):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve.qwen3-4b.decode", "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_entry_refuses_without_a_tpu():
    proc = run_entry(CHECKOUT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_entry_refuses_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench")
    proc = run_entry(tmp_path)
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
