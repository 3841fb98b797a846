"""``tools/kernel_bundles.py``: the reading of the TPU compiler's table
of what each bundle of a kernel uses (no compile here: the table's text
is a sample of what libtpu writes)."""

import pytest

from tools import kernel_bundles as kb

SAMPLE = """== CAPACTIY:
MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU
    4     3     4     1     3     3     1     1     2
== UTILIZATION:
0 0 0 0 0 0 0 0 1
0 0 4 0 3 0 0 0 0
4 1 2 1 0 0 1 1 0
4 3 4 0 1 1 1 0 2
0 0 0 0 0 0 0 0 0
"""


def test_the_table_is_read_a_row_a_bundle():
    units, slots, rows = kb.parse_utilization(SAMPLE)
    assert units[:4] == ["MXU", "XLU", "VALU", "EUP"] and len(units) == 9
    assert slots == [4, 3, 4, 1, 3, 3, 1, 1, 2]
    assert len(rows) == 5 and rows[2] == [4, 1, 2, 1, 0, 0, 1, 1, 0]


def test_a_units_bound_is_its_operations_over_its_slots():
    s = kb.summarize(*kb.parse_utilization(SAMPLE))
    assert s["MXU"] == {"operations": 8, "bound": 2.0, "full": 2, "used": 2}
    assert s["VALU"] == {"operations": 10, "bound": 2.5, "full": 2,
                         "used": 3}
    assert s["VSTORE"]["full"] == 2 and s["VSTORE:SPILL"]["operations"] == 1
    assert s["SALU"] == {"operations": 3, "bound": 1.5, "full": 1,
                         "used": 2}
    text = kb.table(*kb.parse_utilization(SAMPLE))
    assert text.splitlines()[0] == "5 bundles"
    assert len(text.splitlines()) == 2 + 9


def test_a_short_row_is_refused():
    with pytest.raises(ValueError, match="a number a unit"):
        kb.parse_utilization(SAMPLE + "1 2 3\n")


@pytest.mark.parametrize("call", sorted(kb.CALLS))
def test_a_call_names_shapes_the_kernel_takes(call):
    """Each preset is a call of ``paged_flash_attention`` the benchmark's
    cells make: heads in whole kv-head groups, a row block of whole
    sublane tiles, keywords the kernel knows."""
    tokens, h, kvh, d, slots, block_r, batch, kw = kb.CALLS[call]
    assert h % kvh == 0 and d % 128 == 0 and block_r % 8 == 0
    assert tokens >= 1 and slots >= 1 and batch >= 1
    assert set(kw) <= {"window", "v_width"}
