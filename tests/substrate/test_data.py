""".tns I/O round-trip + synthetic dataset structure tests."""
import numpy as np
import pytest

from repro.core import frostt_like, random_sparse
from repro.core.coo import FROSTT_SHAPES
from repro.data import read_tns, write_tns


def test_tns_roundtrip(tmp_path):
    t = random_sparse((12, 9, 7), 200, seed=1)
    path = str(tmp_path / "t.tns")
    write_tns(path, t)
    t2 = read_tns(path)
    # shape inferred from max index can be smaller; indices/values preserved
    np.testing.assert_array_equal(t.indices, t2.indices)
    np.testing.assert_allclose(t.values, t2.values, rtol=1e-6)


def test_tns_gz_and_comments(tmp_path):
    path = str(tmp_path / "t.tns.gz")
    t = random_sparse((5, 5, 5), 30, seed=2)
    write_tns(path, t)
    t2 = read_tns(path)
    assert t2.nnz == 30


def test_tns_rejects_empty(tmp_path):
    p = tmp_path / "e.tns"
    p.write_text("# just a comment\n")
    with pytest.raises(ValueError):
        read_tns(str(p))


@pytest.mark.parametrize("name", list(FROSTT_SHAPES))
def test_frostt_like_structure(name):
    t = frostt_like(name, scale=0.002, seed=0)
    real_shape, _ = FROSTT_SHAPES[name]
    assert t.nmodes == len(real_shape)
    # small dims preserved exactly (they drive scheme selection)
    for got, real in zip(t.shape, real_shape):
        if real <= 2048:
            assert got == real
    assert t.nnz > 0
    # no duplicate coordinates
    dedup = t.deduplicate()
    assert dedup.nnz == t.nnz


@pytest.mark.parametrize("scale,want", [(1.0, (5000, 7)), (0.5, (3000, 7)),
                                        (0.1, (600, 7))])
def test_frostt_like_large_dims_never_exceed_table(monkeypatch, scale, want):
    """A large dim shrinks with scale and never grows past its Table III
    size: scale=1 is the published shape."""
    monkeypatch.setitem(FROSTT_SHAPES, "tiny", ((5000, 7), 6000))
    t = frostt_like("tiny", scale=scale, seed=0)
    assert t.shape == want
