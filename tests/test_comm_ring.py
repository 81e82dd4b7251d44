"""The 8-device comm suite's ring transports (see `_comm_runner.py`):
the flat ring in two subprocesses (the k = 2/4/8 bit-exactness sweeps;
the carries, decode backends, ledgers and dispatch) and the
hierarchical two-axis ring in one."""
import pytest
from _comm_runner import RING_ITEMS, params, run_suite


@pytest.mark.slow
@pytest.mark.parametrize("select,timeout", params(RING_ITEMS))
def test_comm_suite_ring_transport_8_devices(select, timeout):
    run_suite(select, timeout)
