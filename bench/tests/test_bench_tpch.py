"""The benchmark's TPC-H generator against the specification (rev. 3):
cardinalities and value ranges of section 4.2.3, qgen parameter ranges of
sections 2.4.6.3 and 2.4.19.3, refresh sizes of section 2.5."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT]

from bench.data import tpch  # noqa: E402
from bench.reference import Categorical  # noqa: E402

SF = 0.01


@pytest.fixture(scope="module")
def data():
    return tpch.build({"scale_factor": SF}, seed=2**40 + 3)


def test_cardinalities(data):
    assert data.n_orders == 15_000
    assert len(data.part["p_size"]) == 2_000
    lines = np.diff(data.order_start)
    assert lines.min() == 1 and lines.max() == 7
    # mean 4 lines per order: SF1 holds about 6.0M lineitems
    assert abs(data.n / data.n_orders - 4.0) < 0.05
    assert all(len(c) == data.n for c in data.columns.values())


def test_value_ranges(data):
    c = data.columns
    assert c["l_quantity"].min() == 1 and c["l_quantity"].max() == 50
    assert c["l_discount"].min() == 0 and c["l_discount"].max() == 10
    assert c["l_partkey"].min() >= 1 and c["l_partkey"].max() <= 2_000
    assert c["p_size"].min() == 1 and c["p_size"].max() == 50
    # ship date = order date (<= 1998-12-31 - 151 days) + 1..121 days
    assert c["l_shipdate"].min() >= 1
    assert c["l_shipdate"].max() <= tpch.LAST_ORDER_DAY + 121
    assert tpch.LAST_ORDER_DAY == tpch.day(1998, 8, 2)
    assert len(tpch.CONTAINERS) == 40 and len(tpch.BRANDS) == 25
    assert set(c["l_shipmode"].vocab) == {"REG AIR", "AIR", "RAIL", "SHIP",
                                          "TRUCK", "MAIL", "FOB"}
    for name in ("l_shipmode", "l_shipinstruct", "p_brand", "p_container"):
        col = c[name]
        assert isinstance(col, Categorical)
        assert len(np.unique(col.codes)) == len(col.vocab)
    for name, col in c.items():
        arr = col.codes if isinstance(col, Categorical) else col
        if arr.dtype.kind in "iu":
            assert arr.min() >= 0 and arr.max() < 2**24, name


def test_all_lineitem_columns(data):
    c = data.columns
    lineitem = ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
                "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"]
    assert set(lineitem) <= set(c)
    # sparse order keys: the first 8 of every 32, one per order
    keys = np.unique(c["l_orderkey"])
    assert len(keys) == data.n_orders and np.all((keys - 1) % 32 < 8)
    assert c["l_linenumber"].min() == 1 and c["l_linenumber"].max() == 7
    assert c["l_suppkey"].min() >= 1 and c["l_suppkey"].max() <= 100
    assert c["l_tax"].min() == 0 and c["l_tax"].max() == 8
    assert np.array_equal(c["l_extendedprice"], c["l_quantity"]
                          * tpch.retail_cents(c["l_partkey"]))
    gap = c["l_receiptdate"] - c["l_shipdate"]
    assert gap.min() == 1 and gap.max() == 30
    flag = c["l_returnflag"].strings()
    assert set(flag[c["l_receiptdate"] > tpch.CURRENT_DAY]) == {"N"}
    assert set(flag[c["l_receiptdate"] <= tpch.CURRENT_DAY]) == {"A", "R"}
    status = c["l_linestatus"].strings()
    assert np.array_equal(status == "O", c["l_shipdate"] > tpch.CURRENT_DAY)
    lengths = np.char.str_len(c["l_comment"])
    assert lengths.min() >= 9 and lengths.max() <= 43


def test_part_columns_follow_partkey(data):
    c = data.columns
    pidx = c["l_partkey"] - 1
    assert np.array_equal(c["p_size"], data.part["p_size"][pidx])
    assert np.array_equal(c["p_brand"].codes, data.part["p_brand"][pidx])


def test_qgen_parameters():
    rng = np.random.default_rng(7)
    years, discs, qtys = set(), set(), set()
    for _ in range(400):
        q = tpch.q6(rng)
        atoms = {(a[1], a[2]): a[3] for a in q[1]}
        start, end = atoms[("l_shipdate", "ge")], atoms[("l_shipdate", "lt")]
        year = 1992 + [tpch.day(y) for y in range(1992, 2000)].index(start)
        assert end == tpch.day(year + 1)
        years.add(year)
        discs.add(atoms[("l_discount", "ge")] + 1)
        assert atoms[("l_discount", "le")] == atoms[("l_discount", "ge")] + 2
        qtys.add(atoms[("l_quantity", "lt")])
    assert years == set(range(1993, 1998))
    assert discs == set(range(2, 10))
    assert qtys == {24, 25}
    lows = [set(), set(), set()]
    for _ in range(400):
        q = tpch.q19(rng)
        assert q[0] == "or" and len(q[1]) == 3
        for j, arm in enumerate(q[1]):
            atoms = {(a[1], a[2]): a[3] for a in arm[1]}
            lo = atoms[("l_quantity", "ge")]
            assert atoms[("l_quantity", "le")] == lo + 10
            lows[j].add(lo)
            assert atoms[("p_size", "ge")] == 1
            assert atoms[("p_size", "le")] == (5, 10, 15)[j]
            assert atoms[("p_brand", "eq")] in set(tpch.BRANDS)
            assert atoms[("l_shipmode", "in")] == ("AIR", "AIR REG")
            assert atoms[("l_shipinstruct", "eq")] == "DELIVER IN PERSON"
    assert lows == [set(range(1, 11)), set(range(10, 21)), set(range(20, 31))]


def test_refresh_sizes(data):
    rng = np.random.default_rng(1)
    kind, rows = data.mutation("rf1", rng)
    assert kind == "append"
    assert 15 <= len(rows["l_quantity"]) <= 7 * 15
    kind, rows2 = data.mutation("rf2", rng)
    assert kind == "delete"
    assert np.array_equal(rows2, np.arange(data.order_start[15]))
    _, rows3 = data.mutation("rf2", rng)
    assert rows3[0] == data.order_start[15]
