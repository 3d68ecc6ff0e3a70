"""The decision kernels' byte count comes from the unpadded shapes of
the configuration, whatever pads them."""
from bench import etf_ft, harness


def test_shapes_from_the_configuration():
    cfg = harness.load_cell("soc19.etf_grid").cfg
    assert etf_ft.shapes(cfg) == {"R": 16, "P": 19, "MP": 4, "K": 4}


def test_bytes_per_call_unpadded():
    cfg = harness.load_cell("soc19.etf_grid").cfg
    # search: avail and exec [16, 19] f32, free [19], now, slot mask [16]
    # bytes, finish time and index out
    assert etf_ft.bytes_per_call("search", 1, cfg) == \
        4 * (2 * 16 * 19 + 19 + 1) + 16 + 8
    # push: three [4, 4] f32/i32 operands, a [4, 4] mask, PE clusters
    # [19], bases [4], rows [4, 19] out
    assert etf_ft.bytes_per_call("push", 1, cfg) == \
        4 * (3 * 16 + 19 + 4 + 4 * 19) + 16
    assert etf_ft.bytes_per_call("search", 140, cfg) == \
        140 * etf_ft.bytes_per_call("search", 1, cfg)


def test_fault_regime_adds_the_live_pe_mask():
    plain = harness.load_cell("soc19.etf_grid").cfg
    faulty = harness.load_cell("soc19_faults.etf_grid").cfg
    assert etf_ft.bytes_per_call("search", 1, faulty) == \
        etf_ft.bytes_per_call("search", 1, plain) + 19
    assert etf_ft.bytes_per_call("push", 1, faulty) == \
        etf_ft.bytes_per_call("push", 1, plain)
