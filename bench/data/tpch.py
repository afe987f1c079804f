"""TPC-H ``lineitem``, denormalised with the ``part`` columns Q19 reads.

Distributions from the TPC-H specification (revision 3), section 4.2.3:

* ``orders``: SF x 1,500,000 orders, ``o_orderdate`` uniform in
  [1992-01-01, 1998-12-31 - 151 days];
* ``lineitem``: all 16 columns; 1 to 7 lines per order (uniform),
  ``l_orderkey`` sparse (the first 8 of every 32 keys), ``l_partkey``
  uniform in [1, SF x 200,000], ``l_suppkey`` one of the part's 4
  suppliers, ``l_quantity`` uniform in [1, 50], ``l_extendedprice`` =
  quantity x the part's retail price, ``l_discount`` uniform in
  [0.00, 0.10], ``l_tax`` in [0.00, 0.08], ``l_shipdate`` = ``o_orderdate`` +
  [1, 121] days, ``l_commitdate`` = ``o_orderdate`` + [30, 90],
  ``l_receiptdate`` = ship date + [1, 30], ``l_returnflag`` R or A when
  received by CURRENTDATE (1995-06-17) else N, ``l_linestatus`` O when
  shipped after it else F, ``l_shipinstruct`` and ``l_shipmode`` uniform
  over their lists, ``l_comment`` text of 10 to 43 characters;
* ``part``: SF x 200,000 parts, ``p_brand`` = ``Brand#MN`` with M and N
  uniform in [1, 5], ``p_container`` = syllable 1 x syllable 2 (40 values),
  ``p_size`` uniform in [1, 50].

Stored as the configuration states: decimals as scaled integers
(``l_discount`` and ``l_tax`` in hundredths, ``l_extendedprice`` in cents),
dates as days since 1992-01-01, strings as dictionary columns, and
``l_comment`` as ASCII bytes.  Every number is an integer below 2^24.  No
query reads the columns beyond Q6's and Q19's; they are resident as a
deployment holds them, and every append carries them.

Query families: ``q6`` and ``q19`` with the qgen substitution parameters of
sections 2.4.6.3 and 2.4.19.3.  Mutations: ``rf1`` appends the lineitems of
SF x 1,500 new orders; ``rf2`` tombstone-deletes the lineitems of the next
SF x 1,500 orders of the initial population, oldest first (section 2.5).
"""
from __future__ import annotations

import datetime

import numpy as np

from bench.dataset import Dataset
from bench.reference import Categorical

MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])
INSTRUCTIONS = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                         "TAKE BACK RETURN"])
SYLLABLE1 = ("SM", "LG", "MED", "JUMBO", "WRAP")
SYLLABLE2 = ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
CONTAINERS = np.array([f"{a} {b}" for a in SYLLABLE1 for b in SYLLABLE2])
BRANDS = np.array([f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)])

RETURN_FLAGS = np.array(["A", "N", "R"])
LINE_STATUS = np.array(["F", "O"])
#: words of the specification's text grammar (section 4.2.2.13), from which
#: ``l_comment`` takes substrings
WORDS = ("furiously sly careful blithe quick fluffy slow quiet ruthless thin "
         "close dogged daring brave stealthy permanent enticing idle busy "
         "regular final ironic even bold silent foxes ideas theodolites "
         "pinto beans instructions dependencies excuses platelets asymptotes "
         "courts dolphins multipliers sauternes warthogs frets dinos "
         "attainments somas patterns forges braids frays warhorses dugouts "
         "notornis epitaphs pearls tithes waters orbits gifts sheaves depths "
         "sentiments decoys realms pains grouches escapades sleep wake are "
         "cajole haggle nag use boost affix detect integrate maintain nod "
         "was lose sublate solve thrash promise engage hinder print x-ray "
         "breach eat grow impress mold poach serve run dazzle snooze doze "
         "unwind kindle play hang believe doubt about above according across "
         "after against along alongside among around at atop before behind "
         "beneath beside besides between beyond by despite during except "
         "for from inside instead of into near of on outside over past "
         "since through throughout to toward under until up upon without "
         "with within").split()
POOL_BYTES = 1 << 20

EPOCH = datetime.date(1992, 1, 1)
LAST_ORDER_DAY = (datetime.date(1998, 12, 31) - EPOCH).days - 151
CURRENT_DAY = (datetime.date(1995, 6, 17) - EPOCH).days


def day(year: int, month: int = 1, dom: int = 1) -> int:
    """Days since 1992-01-01."""
    return (datetime.date(year, month, dom) - EPOCH).days


class TpchData(Dataset):

    def __init__(self, config: dict, seed: int):
        rng = np.random.default_rng(seed)
        sf = float(config["scale_factor"])
        self.n_orders = int(round(1_500_000 * sf))
        n_parts = int(round(200_000 * sf))
        self.refresh_orders = max(1, int(round(1_500 * sf)))
        self.n_supp = max(4, int(round(10_000 * sf)))
        self.pool = text_pool(rng)
        self._next_order = 0
        self.part = {"p_brand": rng.integers(0, len(BRANDS), n_parts),
                     "p_container": rng.integers(0, len(CONTAINERS), n_parts),
                     "p_size": rng.integers(1, 51, n_parts)}
        counts = rng.integers(1, 8, self.n_orders)
        self.order_start = np.concatenate([[0], np.cumsum(counts)])
        cols = self._lines(rng, counts)
        super().__init__(cols, int(self.order_start[-1]))
        self._deleted_orders = 0

    def _lines(self, rng, counts) -> dict:
        """Lineitem columns for the next ``len(counts)`` orders, with
        ``counts`` lines each."""
        n = int(counts.sum())
        order = self._next_order + np.arange(len(counts))
        self._next_order += len(counts)
        odate = np.repeat(rng.integers(0, LAST_ORDER_DAY + 1, len(counts)),
                          counts)
        first = np.repeat(np.cumsum(counts) - counts, counts)
        partkey = rng.integers(1, len(self.part["p_size"]) + 1, n)
        pidx = partkey - 1
        s = self.n_supp
        supp = rng.integers(0, 4, n)
        quantity = rng.integers(1, 51, n)
        ship = odate + rng.integers(1, 122, n)
        receipt = ship + rng.integers(1, 31, n)
        flag = np.where(receipt <= CURRENT_DAY, rng.integers(0, 2, n) * 2, 1)
        i32 = np.int32
        return {
            "l_orderkey": np.repeat((order // 8) * 32 + order % 8 + 1,
                                    counts).astype(i32),
            "l_partkey": partkey.astype(i32),
            "l_suppkey": ((partkey + supp * (s // 4 + (partkey - 1) // s))
                          % s + 1).astype(i32),
            "l_linenumber": (np.arange(n) - first + 1).astype(i32),
            "l_quantity": quantity.astype(i32),
            "l_extendedprice": (quantity * retail_cents(partkey)).astype(i32),
            "l_discount": rng.integers(0, 11, n).astype(i32),
            "l_tax": rng.integers(0, 9, n).astype(i32),
            "l_returnflag": Categorical(flag.astype(i32), RETURN_FLAGS),
            "l_linestatus": Categorical((ship > CURRENT_DAY).astype(i32),
                                        LINE_STATUS),
            "l_shipdate": ship.astype(i32),
            "l_commitdate": (odate + rng.integers(30, 91, n)).astype(i32),
            "l_receiptdate": receipt.astype(i32),
            "l_shipinstruct": Categorical(
                rng.integers(0, len(INSTRUCTIONS), n).astype(i32),
                INSTRUCTIONS),
            "l_shipmode": Categorical(
                rng.integers(0, len(MODES), n).astype(i32), MODES),
            "l_comment": comments(self.pool, n, rng),
            "p_brand": Categorical(self.part["p_brand"][pidx].astype(i32),
                                   BRANDS),
            "p_container": Categorical(
                self.part["p_container"][pidx].astype(i32), CONTAINERS),
            "p_size": self.part["p_size"][pidx].astype(i32),
        }

    # -- query families ----------------------------------------------------------
    def family(self, name: str, params: dict, rng: np.random.Generator):
        if name == "q6":
            return q6
        if name == "q19":
            return q19
        raise KeyError(f"tpch has no query family {name!r}")

    # -- refresh functions -------------------------------------------------------
    def mutation(self, name: str, rng: np.random.Generator):
        if name == "rf1":
            counts = rng.integers(1, 8, self.refresh_orders)
            return "append", self._lines(rng, counts)
        if name == "rf2":
            lo = self._deleted_orders
            hi = min(lo + self.refresh_orders, self.n_orders)
            self._deleted_orders = hi
            return "delete", np.arange(self.order_start[lo],
                                       self.order_start[hi])
        raise KeyError(f"tpch has no mutation {name!r}")


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """``p_retailprice`` in cents (section 4.2.3)."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def text_pool(rng: np.random.Generator) -> np.ndarray:
    """``POOL_BYTES`` of the grammar's words, as bytes."""
    words = np.array(WORDS)
    n = POOL_BYTES // 6
    text = " ".join(words[rng.integers(0, len(words), n)]).encode()
    return np.frombuffer(text[:POOL_BYTES], np.uint8)


def comments(pool: np.ndarray, n: int, rng: np.random.Generator
             ) -> np.ndarray:
    """``n`` comments: substrings of the pool at random offsets, of 10 to
    43 characters, as dbgen takes them from its text pool."""
    width = 43
    start = rng.integers(0, len(pool) - width, n)
    length = rng.integers(10, width + 1, n)
    raw = pool[start[:, None] + np.arange(width)]
    raw[np.arange(width) >= length[:, None]] = 0
    return np.ascontiguousarray(raw).view(f"S{width}").ravel()


def q6(rng: np.random.Generator):
    """Q6 (section 2.4.6): DATE the first of January of a year in
    [1993, 1997], DISCOUNT in [0.02, 0.09], QUANTITY in [24, 25]."""
    year = int(rng.integers(1993, 1998))
    disc = int(rng.integers(2, 10))
    qty = int(rng.integers(24, 26))
    return ("and", (
        ("atom", "l_shipdate", "ge", day(year)),
        ("atom", "l_shipdate", "lt", day(year + 1)),
        ("atom", "l_discount", "ge", disc - 1),
        ("atom", "l_discount", "le", disc + 1),
        ("atom", "l_quantity", "lt", qty)))


def _brand(rng) -> str:
    return f"Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}"


def q19(rng: np.random.Generator):
    """Q19 (section 2.4.19) as the specification writes it, with the atoms
    its three arms repeat; ``p_partkey = l_partkey`` holds by construction
    of the denormalised row.  QUANTITY1 in [1, 10], QUANTITY2 in [10, 20],
    QUANTITY3 in [20, 30], BRANDj = Brand#MN with M, N in [1, 5]."""
    arms = (("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 5,
            int(rng.integers(1, 11))), \
           (("MED BAG", "MED BOX", "MED PKG", "MED PACK"), 10,
            int(rng.integers(10, 21))), \
           (("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 15,
            int(rng.integers(20, 31)))
    return ("or", tuple(
        ("and", (
            ("atom", "p_brand", "eq", _brand(rng)),
            ("atom", "p_container", "in", containers),
            ("atom", "l_quantity", "ge", qty),
            ("atom", "l_quantity", "le", qty + 10),
            ("atom", "p_size", "ge", 1),
            ("atom", "p_size", "le", size),
            ("atom", "l_shipmode", "in", ("AIR", "AIR REG")),
            ("atom", "l_shipinstruct", "eq", "DELIVER IN PERSON")))
        for containers, size, qty in arms))


def build(config: dict, seed: int) -> TpchData:
    return TpchData(config, seed)
