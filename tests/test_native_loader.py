"""Native C++ CSV loader (fedtpu.native) parity with the pandas path: both
must produce identical matrices, column typing, and LabelEncoder classes on
the shipped income CSV and on synthetic edge-case CSVs (quoting, CRLF,
missing trailing newline, empty cells)."""

import dataclasses

import numpy as np
import pytest

from fedtpu import native
from fedtpu.config import DataConfig, default_income_csv
from fedtpu.data.tabular import _load_encoded, load_tabular_dataset

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no C++ toolchain")


def _both(path):
    cols_n, mat_n, cls_n, parser_n = _load_encoded(path, use_native=True)
    cols_p, mat_p, cls_p, parser_p = _load_encoded(path, use_native=False)
    assert (parser_n, parser_p) == ("native", "pandas")
    return (cols_n, mat_n, cls_n), (cols_p, mat_p, cls_p)


def test_income_csv_native_matches_pandas():
    path = default_income_csv()
    if path is None:
        pytest.skip("income CSV not present")
    (cols_n, mat_n, cls_n), (cols_p, mat_p, cls_p) = _both(path)
    assert cols_n == cols_p
    np.testing.assert_array_equal(mat_n, mat_p)
    assert set(cls_n) == set(cls_p)
    for k in cls_n:
        np.testing.assert_array_equal(np.asarray(cls_n[k], dtype=object),
                                      np.asarray(cls_p[k], dtype=object))


def test_generated_income_csv_native_matches_pandas(tmp_path):
    """The reference's CSV is not shipped, so the byte-for-byte parity of
    the two parsers is held on the seeded CSV of the same shape that
    chip_smoke.py trains on (10,000 rows, 8 string columns + the label)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chip_smoke import write_income_csv

    path = str(tmp_path / "income.csv")
    write_income_csv(path, seed=3)
    (cols_n, mat_n, cls_n), (cols_p, mat_p, cls_p) = _both(path)
    assert cols_n == cols_p and mat_n.shape == (10_000, 15)
    np.testing.assert_array_equal(mat_n, mat_p)
    assert set(cls_n) == set(cls_p) and len(cls_n) == 9
    for k in cls_n:
        np.testing.assert_array_equal(np.asarray(cls_n[k], dtype=object),
                                      np.asarray(cls_p[k], dtype=object))


def test_quoting_crlf_and_missing_trailing_newline(tmp_path):
    p = tmp_path / "edge.csv"
    p.write_bytes(b'a,b,c\r\n1,"x,y",3.5\r\n2,"say ""hi""",\r\n3,z,7')
    cols, mat, cls, _ = _load_encoded(str(p), use_native=True)
    assert cols == ["a", "b", "c"]
    # b is categorical with sorted-unique codes; c has an empty cell -> NaN.
    np.testing.assert_array_equal(mat[:, 0], [1.0, 2.0, 3.0])
    order = sorted(['x,y', 'say "hi"', 'z'])
    np.testing.assert_array_equal(mat[:, 1],
                                  [order.index('x,y'),
                                   order.index('say "hi"'),
                                   order.index('z')])
    assert mat[0, 2] == 3.5 and np.isnan(mat[1, 2]) and mat[2, 2] == 7.0
    assert list(cls["b"]) == order


def test_blank_lines_skipped_like_pandas(tmp_path):
    p = tmp_path / "blank.csv"
    p.write_text("a,b\n1,x\n\n2,y\n\n")
    (cols_n, mat_n, _), (cols_p, mat_p, _) = _both(str(p))
    assert cols_n == cols_p
    np.testing.assert_array_equal(mat_n, mat_p)
    assert mat_n.shape == (2, 2)


def test_hex_literals_stay_categorical_like_pandas(tmp_path):
    p = tmp_path / "hex.csv"
    p.write_text("a,b\n0x10,1\n0x2A,2\n")
    (cols_n, mat_n, cls_n), (cols_p, mat_p, cls_p) = _both(str(p))
    np.testing.assert_array_equal(mat_n, mat_p)
    np.testing.assert_array_equal(np.asarray(cls_n["a"], dtype=object),
                                  np.asarray(cls_p["a"], dtype=object))


def test_embedded_newline_in_quoted_field_classes_survive(tmp_path):
    p = tmp_path / "nl.csv"
    p.write_bytes(b'a,b\n1,"x\ny"\n2,z\n')
    cols, mat, cls, _ = _load_encoded(str(p), use_native=True)
    assert list(cls["b"]) == sorted(["x\ny", "z"])
    np.testing.assert_array_equal(
        mat[:, 1], [sorted(["x\ny", "z"]).index("x\ny"),
                    sorted(["x\ny", "z"]).index("z")])


def test_ragged_row_is_an_error(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text("a,b\n1,2\n3\n")
    with pytest.raises(ValueError, match="ragged"):
        _load_encoded(str(p), use_native=True)


def test_end_to_end_dataset_identical_with_either_loader():
    path = default_income_csv()
    if path is None:
        pytest.skip("income CSV not present")
    ds_n = load_tabular_dataset(DataConfig(csv_path=path))
    ds_p = load_tabular_dataset(
        dataclasses.replace(DataConfig(csv_path=path), native_loader=False))
    np.testing.assert_array_equal(ds_n.x_train, ds_p.x_train)
    np.testing.assert_array_equal(ds_n.y_train, ds_p.y_train)
    np.testing.assert_array_equal(ds_n.x_test, ds_p.x_test)
    np.testing.assert_array_equal(ds_n.label_classes, ds_p.label_classes)
