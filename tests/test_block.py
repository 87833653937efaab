import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.block import Block, Dictionary, Page, padded_size


def test_padded_size_buckets():
    assert padded_size(0) == 16
    assert padded_size(16) == 16
    assert padded_size(17) == 32
    assert padded_size(1000) == 1024


def test_dictionary_roundtrip():
    d = Dictionary()
    codes = d.encode(["apple", "banana", "apple", None])
    assert codes.tolist() == [0, 1, 0, 0]
    assert d.decode(np.array([1, 0])) == ["banana", "apple"]
    assert d.lookup("cherry") == -1
    assert d.code("cherry") == 2


def test_dictionary_sort_rank():
    d = Dictionary(["pear", "apple", "mango"])
    rank = d.sort_rank()
    # apple < mango < pear
    assert rank.tolist() == [2, 0, 1]


def test_block_pylist_roundtrip():
    b = Block.from_pylist(T.BIGINT, [1, None, 3])
    assert b.to_pylist() == [1, None, 3]
    assert b.may_have_nulls

    s = Block.from_pylist(T.VARCHAR, ["x", "y", None, "x"])
    assert s.to_pylist() == ["x", "y", None, "x"]

    d = Block.from_pylist(T.decimal_type(10, 2), ["1.50", None])
    from decimal import Decimal
    assert d.to_pylist() == [Decimal("1.50"), None]


def test_block_region_take_filter():
    b = Block.from_pylist(T.INTEGER, [10, 20, 30, 40, 50])
    assert b.region(1, 3).to_pylist() == [20, 30, 40]
    assert b.take([4, 0]).to_pylist() == [50, 10]
    assert b.filter([True, False, True, False, False]).to_pylist() == [10, 30]


def test_page_ops():
    p = Page.from_pylists(
        [T.BIGINT, T.VARCHAR],
        [[1, 2, 3], ["a", "b", "a"]],
    )
    assert p.num_rows == 3 and p.channel_count == 2
    assert p.to_rows() == [(1, "a"), (2, "b"), (3, "a")]
    assert p.filter([False, True, True]).to_rows() == [(2, "b"), (3, "a")]
    assert p.select_channels([1]).to_rows() == [("a",), ("b",), ("a",)]


def test_page_concat_unifies_dictionaries():
    p1 = Page.from_pylists([T.VARCHAR], [["a", "b"]])
    p2 = Page.from_pylists([T.VARCHAR], [["b", "c"]])
    out = Page.concat([p1, p2])
    assert out.num_rows == 4
    assert out.block(0).to_pylist() == ["a", "b", "b", "c"]
    assert out.block(0).dictionary is p1.block(0).dictionary


def test_page_concat_with_nulls():
    p1 = Page.from_pylists([T.BIGINT], [[1, None]])
    p2 = Page.from_pylists([T.BIGINT], [[3]])
    out = Page.concat([p1, p2])
    assert out.block(0).to_pylist() == [1, None, 3]


def _encode_one_by_one(d, strings, null_value=""):
    """``encode`` as it was: ``code()`` a value, in order."""
    out = []
    for s in strings:
        if s is None:
            if not d.values:
                d.code(null_value)
            out.append(0)
        else:
            out.append(d.code(s))
    return out


@pytest.mark.parametrize("pool, strings, null_value", [
    ([], ["a", "b", "a", "c"], ""),
    ([], [None, "a", None, "b", "a"], ""),       # NULL first: "" is code 0
    ([], ["a", None, "b"], ""),                  # NULL later: code 0 is "a"
    ([], [], ""),
    ([], [None], ""),
    (["p", "q"], ["q", "p", None, "q"], ""),      # nothing new: no lock taken
    (["p", "q"], ["r", "q", "r", "s"], ""),
    ([], [(1, 2), None, (3,), (1, 2)], ()),
], ids=["new", "null_first", "null_later", "empty", "only_null",
        "all_known", "some_new", "tuples"])
def test_encode_a_batch_equals_code_a_value(pool, strings, null_value):
    batch, single = Dictionary(pool), Dictionary(pool)
    got = batch.encode(strings, null_value=null_value)
    assert got.dtype == np.int32 and got.shape == (len(strings),)
    assert got.tolist() == _encode_one_by_one(single, strings, null_value)
    assert list(batch.values) == list(single.values)
    assert batch.decode(got[[s is not None for s in strings]]) == \
        [s for s in strings if s is not None]


def test_concurrent_encoders_grow_one_pool_consistently():
    """Four scan tasks growing one connector pool (a distributed CTAS):
    every value gets one code, whichever thread met it first."""
    import threading

    d = Dictionary()
    batches = [[f"v{(7 * t + i) % 5000}" for i in range(20000)]
               for t in range(4)]
    codes = [None] * 4

    def run(t):
        codes[t] = d.encode(batches[t])

    threads = [threading.Thread(target=run, args=(t,)) for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(d.values) == len(set(d.values)) == 5000
    for t in range(4):
        assert d.decode(codes[t]) == batches[t]
