"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHECKOUT = Path(__file__).resolve().parents[2]
for p in (str(CHECKOUT), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture
def no_cache(monkeypatch):
    """Runs here leave the repository's compile cache alone."""
    import bench.harness as h
    monkeypatch.setattr(h, "enable_cache", lambda: "off")


@pytest.fixture(scope="session")
def data_bench():
    from bench.harness import BENCH, Benchmark
    cell = BENCH / "testdata" / "cell"
    return Benchmark(cell / "BENCHMARK.json", cell)
