"""`bytes_per_row` arithmetic: the narrowest machine widths (1, 2, 4
bytes) of the columns a text must read, counted as ISSUE 25 counts them
over the domains dbgen's population gives (`l_quantity` 1..50: 1 byte)."""

import pytest

from benchmark.tests.cells import cell_of
from benchmark.lib.traffic import build_streams, bytes_per_row


@pytest.mark.parametrize("cell,template,want", [
    ("tpch_scan_streams", "q6", {"lineitem": 8}),
    ("tpch_scan_streams", "q1", {"lineitem": 11}),
    ("tpch_scan_streams", "topn", {"lineitem": 6}),
    ("tpch_q3_streams", "q3", {"lineitem": 11, "orders": 11, "customer": 5}),
])
def test_bytes_per_row(cell, template, want):
    _, _, config, mix = cell_of(cell)
    assert bytes_per_row(mix, config, template) == want


def test_statement_rows_and_bytes():
    _, _, config, mix = cell_of("tpch_q3_streams")
    (s0,), (s1,) = build_streams(mix, config)
    assert s0.rows_read == 16_000_000 + 4_000_000 + 400_000
    assert s0.bytes_needed == 16_000_000 * 11 + 4_000_000 * 11 + 400_000 * 5
    assert "BUILDING" in s0.sql and "MACHINERY" in s1.sql and s0.sql != s1.sql


def test_scan_mix_has_ten_texts_none_shared():
    _, _, config, mix = cell_of("tpch_scan_streams")
    streams = build_streams(mix, config)
    texts = [s.sql for st in streams for s in st]
    assert [len(st) for st in streams] == [5, 5] and len(set(texts)) == 10


def test_a_shared_text_is_refused():
    _, _, config, mix = cell_of("tpch_q3_streams")
    mix = dict(mix, streams=[mix["streams"][0], mix["streams"][0]])
    with pytest.raises(ValueError, match="twice"):
        build_streams(mix, config)
