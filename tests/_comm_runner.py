"""Runner for the multi-device compressed-collective suite.

The suite (`_comm_suite.py`) needs 8 forced host devices, which must be
set before jax initializes, so it runs in subprocesses; the main pytest
process keeps the real 1-device view (the project convention).  Each
outer item runs one ``-k`` selection of the suite in a subprocess with
its own time limit, and the items are spread over three test files so
that ``pytest -n N --dist loadfile`` runs the files on different
workers at once:

  file                selection (-k)                   cases      s  limit
  test_comm.py        TestLedgerCollectives                4    9.4     60
                      TestBitexactCollectives              3   12.1     60
                      TestStreamingChunkedCollectives      4   23.9     60
                      TestOtherCollectives                 3    8.5     60
                      TestLifecycleEpochAgreement          1    5.9     60
  test_comm_ring.py   TestRingTransport, the k sweeps     12   35.8     90
                      TestRingTransport, the rest         13   68.3    150
                      TestHierarchicalRing                 6   17.7     60
  test_comm_family.py TestRingReduceScatter                8   31.8     90
                      TestRingAllToAll                     8   26.3     60
                      TestMoEDispatchA2A                   4   84.5    180

"s" is the item's wall time, subprocess start-up included, measured
in a run of the whole tier-1 suite on an 8-core CPU host
(``-n 6 --dist loadfile``, 290 s in all; the files took 60, 122 and
142 s).  The limit is twice that rounded up to 30 s, at least 60 s
(start-up and first compiles vary most on a loaded host), and never
more than 600 s.  A new class in `_comm_suite.py` gets an item
in the file with the least time; ``test_comm.py`` checks that every
class has one.
"""
import os
import pathlib
import subprocess
import sys

import pytest

SUITE = pathlib.Path(__file__).parent / "_comm_suite.py"

_SWEEPS = ("test_ring_all_gather_bitexact or "
           "test_ring_all_reduce_bitexact_vs_psum")

# id -> (-k selection, limit in s), one outer item each; see the table
ENDPOINT_ITEMS = {
    "ledger": ("TestLedgerCollectives", 60),
    "bitexact": ("TestBitexactCollectives", 60),
    "streaming": ("TestStreamingChunkedCollectives", 60),
    "other": ("TestOtherCollectives", 60),
    "lifecycle": ("TestLifecycleEpochAgreement", 60),
}
RING_ITEMS = {
    "sweeps": (f"TestRingTransport and ({_SWEEPS})", 90),
    "rest": (f"TestRingTransport and not ({_SWEEPS})", 150),
    "hierarchical": ("TestHierarchicalRing", 60),
}
FAMILY_ITEMS = {
    "reduce_scatter": ("TestRingReduceScatter", 90),
    "all_to_all": ("TestRingAllToAll", 60),
    "moe_a2a": ("TestMoEDispatchA2A", 180),
}


def params(items):
    """``pytest.param(select, timeout, id=...)`` for each item."""
    return [pytest.param(select, timeout, id=name)
            for name, (select, timeout) in items.items()]


def _text(out) -> str:
    # TimeoutExpired carries the output read so far as bytes (or None)
    if isinstance(out, bytes):
        return out.decode(errors="replace")
    return out or ""


def run_suite(select: str, timeout: float) -> None:
    """Run the suite's ``-k select`` cases on 8 forced host devices; fail
    with the subprocess's output if a case fails or it outlives
    ``timeout`` seconds."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parents[1] / "src")
    env["PYTHONUNBUFFERED"] = "1"     # a killed run still shows its progress
    cmd = [sys.executable, str(SUITE), "-v", "-p", "no:cacheprovider",
           "-k", select]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"comm suite (-k {select!r}) ran past its {timeout} s "
                    f"limit\nstdout:\n{_text(e.stdout)}\n"
                    f"stderr:\n{_text(e.stderr)}", pytrace=False)
    assert proc.returncode == 0, (
        f"comm suite (-k {select!r}) failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
