"""Types + Page/Column + serde golden tests (SURVEY.md §7.2 step 1)."""
import datetime
from decimal import Decimal

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.data import Column, Dictionary, Page
from trino_tpu.data.serde import CODEC_NONE, deserialize_page, serialize_page


def test_parse_types():
    assert T.parse_type("bigint") is T.BIGINT
    assert T.parse_type("decimal(15,2)").scale == 2
    assert T.parse_type("varchar(25)").length == 25
    assert T.parse_type("double") is T.DOUBLE
    with pytest.raises(ValueError):
        T.parse_type("frobnicate")


def test_common_super_type():
    assert T.common_super_type(T.INTEGER, T.BIGINT) == T.BIGINT
    assert T.common_super_type(T.BIGINT, T.DOUBLE) == T.DOUBLE
    d = T.common_super_type(T.decimal(15, 2), T.decimal(10, 4))
    assert (d.precision, d.scale) == (17, 4)
    assert T.common_super_type(T.UNKNOWN, T.DATE) == T.DATE
    assert T.common_super_type(T.BOOLEAN, T.BIGINT) is None


def test_column_roundtrip_fixed_width():
    col = Column.from_python(T.BIGINT, [1, 2, None, 4])
    assert col.to_python() == [1, 2, None, 4]
    col = Column.from_python(T.DOUBLE, [1.5, -2.25])
    assert col.to_python() == [1.5, -2.25]
    col = Column.from_python(T.BOOLEAN, [True, None, False])
    assert col.to_python() == [True, None, False]


def test_column_roundtrip_date_decimal():
    col = Column.from_python(T.DATE, ["1994-01-01", datetime.date(1998, 12, 1), None])
    assert col.to_python() == [datetime.date(1994, 1, 1), datetime.date(1998, 12, 1), None]
    dec = T.decimal(15, 2)
    col = Column.from_python(dec, ["1.50", "-7.25", None])
    assert col.to_python() == [Decimal("1.50"), Decimal("-7.25"), None]
    assert np.asarray(col.values)[:2].tolist() == [150, -725]


def test_varchar_dictionary_order():
    col = Column.from_python(T.VARCHAR, ["beta", "alpha", None, "beta", "gamma"])
    assert col.to_python() == ["beta", "alpha", None, "beta", "gamma"]
    # dictionary codes preserve string order (dictionary-first design)
    d = col.dictionary
    assert d.values == sorted(d.values)
    assert d.code_of("alpha") < d.code_of("beta") < d.code_of("gamma")


def test_page_sel_mask():
    import jax.numpy as jnp

    page = Page.from_pydict(
        {"a": T.BIGINT, "b": T.VARCHAR},
        {"a": [1, 2, 3], "b": ["x", "y", "z"]},
    )
    assert page.num_rows == 3 and page.channel_count == 2
    page.sel = jnp.asarray(np.array([True, False, True]))
    assert page.live_count() == 2
    assert page.to_pylist() == [(1, "x"), (3, "z")]


@pytest.mark.parametrize("codec", [CODEC_NONE, 1])
def test_serde_roundtrip(codec):
    page = Page.from_pydict(
        {
            "k": T.BIGINT,
            "s": T.VARCHAR,
            "d": T.DATE,
            "m": T.decimal(15, 2),
            "f": T.DOUBLE,
        },
        {
            "k": [10, None, 30],
            "s": ["foo", "bar", None],
            "d": ["1995-03-15", None, "1992-01-02"],
            "m": ["1.10", "2.20", None],
            "f": [0.5, None, -1.0],
        },
    )
    blob = serialize_page(page, codec=codec)
    back = deserialize_page(blob)
    assert back.num_rows == 3
    for orig, rt in zip(page.columns, back.columns):
        assert str(orig.type) == str(rt.type)
        assert orig.to_python() == rt.to_python()


@pytest.mark.parametrize("name", ["v2", "v3-zlib", "v3-raw"])
def test_frames_of_an_older_process_still_deserialize(name):
    """Spool files and result segments outlive the process that wrote
    them: versions 2 and 3 (a length before each vocabulary entry) read
    as they always did."""
    from tests.legacy_frames import FRAMES, ROWS

    frame = FRAMES[name]
    assert frame[4] == int(name[1])
    assert deserialize_page(frame).to_pylist() == ROWS
    # and what is written now reads the same rows back
    back = deserialize_page(serialize_page(deserialize_page(frame)))
    assert back.to_pylist() == ROWS


VOCABULARIES = {
    "ascii": ["a", "bb", "ccc"],
    "empty-strings": ["", "a", "b"],
    "only-the-empty-string": [""],
    "non-ascii": ["", "naïve", "żółw", "日本語", "🙂 smile", "plain"],
    "one-entry": ["x" * 300],
    "no-entry": [],
}


@pytest.mark.parametrize("codec", [CODEC_NONE, 1])
@pytest.mark.parametrize("case", sorted(VOCABULARIES))
def test_a_vocabulary_is_written_as_one_block(case, codec):
    """Wire version 4: ``dict_len``, the entries' byte lengths as one u32
    array, then the entries end to end; NULL codes stay -1."""
    import struct

    from trino_tpu.data.serde import VERSION

    vocab = sorted(VOCABULARIES[case])
    codes = np.array([-1] + list(range(len(vocab))) + [-1], np.int32)
    nulls = codes < 0
    page = Page([Column(T.VARCHAR, codes, nulls, Dictionary(vocab))])
    blob = serialize_page(page, codec=codec)
    assert blob[4] == VERSION == 4
    back = deserialize_page(blob)
    assert back.columns[0].dictionary.values == vocab
    assert back.to_pylist() == [(None,)] + [(s,) for s in vocab] + [(None,)]
    if codec == CODEC_NONE:
        # the block's tail IS the vocabulary block
        blobs = [s.encode() for s in vocab]
        tail = (struct.pack("<I", len(vocab))
                + np.array([len(b) for b in blobs], "<u4").tobytes()
                + b"".join(blobs))
        assert blob.endswith(tail)


@pytest.mark.parametrize("live", [[], [5], [5, 7, 70_000, 199_999],
                                  list(range(0, 200_000, 7))])
def test_few_live_rows_of_a_large_device_page_are_taken_on_the_device(live):
    """``to_pylist`` of a point lookup's page: the mask is read, the live
    rows are gathered on the device at a power-of-two bucket and come back
    in ONE batched read; the rows are what the host gather answers."""
    import jax.numpy as jnp

    from trino_tpu.data.page import DEVICE_TAKE_MIN_ROWS
    from trino_tpu.obs.devprofiler import charge_to, new_kernel_row

    n = 200_000
    assert n >= DEVICE_TAKE_MIN_ROWS
    arrays = {
        "k": np.arange(n, dtype=np.int64), "null": np.arange(n) % 7 == 0,
        "code": (np.arange(n) % 100).astype(np.int32),
        "hi": np.arange(n, dtype=np.int64) % 3 - 1}
    vocab = Dictionary([f"v{i:03d}" for i in range(100)])

    def page(xp):
        a = {k: xp.asarray(v) for k, v in arrays.items()}
        return Page([
            Column(T.BIGINT, a["k"], a["null"], ascending=True),
            Column(T.VARCHAR, a["code"], None, vocab),
            Column(T.decimal(38, 2), a["k"], None, hi=a["hi"]),
        ], xp.asarray(np.isin(np.arange(n), live)))

    row = new_kernel_row("0", "Output", "eager")
    with charge_to(row):
        got = page(jnp).to_pylist()
    assert got == page(np).to_pylist() and len(got) == len(live)
    few = 8 * len(live) <= n
    # the mask, then one batch (or, past an eighth live, a read an array)
    assert row["hostSyncSites"]["result-rows"][0] == (2 if few else 6)
    if few:
        assert row["d2hBytes"] < n + 64 * len(live) + 64


def test_dictionary_recode():
    a = Dictionary.build(["apple", "pear"])
    b = Dictionary.build(["pear", "apple", "fig"])
    table = a.recode_table(b)
    assert b.decode_one(table[a.code_of("apple")]) == "apple"
    assert b.decode_one(table[a.code_of("pear")]) == "pear"
