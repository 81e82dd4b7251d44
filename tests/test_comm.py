"""The 8-device comm suite's endpoint transports (ledger, bitexact,
streaming chunked, the other collectives) and the lifecycle epoch
agreement, one subprocess per class (see `_comm_runner.py`); and the
check that every class of the suite has an outer item."""
import ast

import pytest
from _comm_runner import (ENDPOINT_ITEMS, FAMILY_ITEMS, RING_ITEMS, SUITE,
                          params, run_suite)


@pytest.mark.slow
@pytest.mark.parametrize("select,timeout", params(ENDPOINT_ITEMS))
def test_comm_suite_8_devices(select, timeout):
    run_suite(select, timeout)


def test_every_suite_class_has_an_item():
    tree = ast.parse(SUITE.read_text())
    classes = {node.name for node in tree.body
               if isinstance(node, ast.ClassDef)
               and node.name.startswith("Test")}
    selected = {select.split()[0]
                for items in (ENDPOINT_ITEMS, RING_ITEMS, FAMILY_ITEMS)
                for select, _ in items.values()}
    assert selected == classes
