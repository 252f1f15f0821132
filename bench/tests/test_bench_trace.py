"""Trace reduction on synthetic events: busy union, idle gaps labelled by
host span, kernel time, the costliest operations."""
import pytest

from bench import trace as tr

DEV = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 10.0),   # overlap: 0-15
       ("_dequant_kernel", 30.0, 5.0),                      # 30-35
       ("_quant_pack_kernel", 50.0, 20.0),                  # 50-70
       ("fusion.1", 90.0, 10.0)]                            # 90-100
HOST = [("segment", 0.0, 100.0), ("tick", 14.0, 20.0),
        ("fetch", 36.0, 14.0), ("admit", 70.0, 25.0)]


def test_busy_union_merges_overlaps():
    assert tr.intervals(DEV) == [(0.0, 15.0), (30.0, 35.0), (50.0, 70.0),
                                 (90.0, 100.0)]
    assert tr.busy_ns(DEV) == 50.0


def test_gaps_cover_the_rest_of_the_window():
    g = tr.gaps(DEV, 0.0, 110.0)
    assert g == [(15.0, 30.0), (35.0, 50.0), (70.0, 90.0), (100.0, 110.0)]
    assert tr.busy_ns(DEV) + sum(b - a for a, b in g) == 110.0


def test_clip_cuts_to_the_window():
    assert tr.busy_ns(tr.clip(DEV, 8.0, 60.0)) == (15 - 8) + 5 + 10


def test_gaps_are_labelled_by_the_innermost_covering_span():
    top = tr.top_gaps(DEV, HOST, 0.0, 100.0)
    assert [n for n, _ in top] == ["admit", "tick", "fetch"]
    assert [s for _, s in top] == pytest.approx([20e-9, 15e-9, 15e-9])
    assert tr.label_gap((200.0, 210.0), HOST) == "host"


def test_kernel_time_and_top_ops():
    assert tr.kernel_ns(DEV, ("_quant_pack_kernel", "_dequant_kernel")) == 25
    assert tr.top_ops(DEV, 2) == [("fusion.1", 20e-9), ("_quant_pack_kernel",
                                                        20e-9)]
