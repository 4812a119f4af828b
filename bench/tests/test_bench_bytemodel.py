"""The byte model against shapes worked by hand."""

from bench.metrics import bytemodel


def test_lss_state_bytes_by_hand():
    # one tenant, one peer, one slot, d=1: x (2 floats) + out and in
    # (2 x 2 floats) + live flag (1) read; status (2 floats) + violation
    # flag (1) + decision (1) written.
    assert bytemodel.lss_state_bytes(1, 1, 1, 1) == 8 + 16 + 1 + 8 + 1 + 1
    # grid80k.stream: 16 tenants, 80,089 peers, 4 slots, d=2.
    per_peer = 12 + 2 * 4 * 12 + 4 + 12 + 4 + 1
    assert bytemodel.lss_state_bytes(16, 80089, 4, 2) == 16 * 80089 * per_peer


def test_correction_bytes_by_hand():
    # status (2 floats), agreement and in-message (2 x 2 floats), flag (1)
    # read; the new out-message (2 floats) written.
    assert bytemodel.correction_bytes(1, 1, 1, 1) == 8 + 16 + 1 + 8
    per_peer = 12 + 2 * 34 * 12 + 34 + 34 * 12
    assert bytemodel.correction_bytes(8, 80000, 34, 2) == 8 * 80000 * per_peer


def test_bytes_scale_linearly_in_tenants_and_peers():
    for f in (bytemodel.lss_state_bytes, bytemodel.correction_bytes):
        assert f(4, 100, 6, 2) == 4 * f(1, 100, 6, 2)
        assert f(1, 300, 6, 2) == 3 * f(1, 100, 6, 2)
