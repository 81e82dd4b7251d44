"""The 8-device comm suite's ring family: reduce_scatter, all_to_all
and the MoE a2a dispatch wire, one subprocess per class (see
`_comm_runner.py`)."""
import pytest
from _comm_runner import FAMILY_ITEMS, params, run_suite


@pytest.mark.slow
@pytest.mark.parametrize("select,timeout", params(FAMILY_ITEMS))
def test_comm_suite_ring_family_8_devices(select, timeout):
    run_suite(select, timeout)
