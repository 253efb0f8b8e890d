"""TPC-H LINEITEM, ORDERS and CUSTOMER as dbgen populates them (spec v3,
4.2.3), every column of 1.4.1, seeded from `--seed`.

What the spec fixes is kept: sparse order keys (the first 8 of every 32),
1 to 7 lineitems an order, `o_custkey` never a multiple of 3,
`o_orderdate` uniform over STARTDATE..ENDDATE-151, `l_shipdate` =
`o_orderdate` + 1..121, `l_commitdate` = `o_orderdate` + 30..90,
`l_receiptdate` = `l_shipdate` + 1..30, `l_returnflag` and `l_linestatus`
derived from those dates against CURRENTDATE, `l_extendedprice` =
`l_quantity` x the part's retail price, `l_suppkey` from the part key,
`o_orderstatus` and `o_totalprice` from the order's lineitems, comments
cut from a pool of the grammar's text (4.2.2.10) as dbgen cuts them.
What differs from dbgen is its random stream: the draws come from numpy,
so a seed gives other rows than dbgen's fixed seeds, of the same
distributions. The lineitem count of every order is a permutation of one
fixed multiset, uniform over 1..7, and the permutation is drawn from the
sizes, NOT from the seed: every seed has exactly the configuration's row
count and the same orders of the same sizes at the same rows, so that
`l_orderkey` (a function of the order's number) is the same lane for
every seed. The program's compiled shapes hold the row count, and its
program keys hold each lane's codec: a 2^21-row region holds 2^19 +- 400
orders, `l_orderkey`'s run-length code pads to 2^19 or 2^20 runs on
either side of that, and a seed that moved a region across it changed
the programs compiled and the work of every TopN (PERF.md, section 6,
PR 29). Every value of every other column still comes from the seed.

A configuration names a generator per table as `<module>.<function>`
(`configs/*.json`, `tables[].generator`); a new family of tables is a new
module beside this one. The tables of one seed are made together and
handed out one by one. Decimals are scaled integers (scale 2), dates are
the program's packed-time int64 (`((y*13+m)*32+d) * US_DAY`), CHAR and
VARCHAR columns are numpy byte strings (`S<width>`).
"""

from __future__ import annotations

import random

import numpy as np

US_DAY = 24 * 60 * 60 * 1_000_000

RETURNFLAGS = np.array([b"A", b"N", b"R"])
LINESTATUS = np.array([b"F", b"O"])
ORDERSTATUS = np.array([b"F", b"O", b"P"])
SEGMENTS = np.array([b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY", b"HOUSEHOLD"])
PRIORITIES = np.array([b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED", b"5-LOW"])
INSTRUCTIONS = np.array([b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"])
MODES = np.array([b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"])

STARTDATE, CURRENTDATE, ENDDATE = "1992-01-01", "1995-06-17", "1998-12-31"
_DAY0 = np.datetime64(STARTDATE, "D")
CURRENT_DAY = int((np.datetime64(CURRENTDATE, "D") - _DAY0).astype(int))
LAST_ORDER_DAY = int((np.datetime64(ENDDATE, "D") - _DAY0).astype(int)) - 151


def packed_date(text: str) -> int:
    """'YYYY-MM-DD' -> the packed-time int64 the generators emit."""
    y, m, d = (int(x) for x in text.split("-"))
    return ((y * 13 + m) * 32 + d) * US_DAY


def date_text(packed: int) -> str:
    v = int(packed) // US_DAY
    return f"{v // 32 // 13:04d}-{v // 32 % 13:02d}-{v % 32:02d}"


def _packed_days() -> np.ndarray:
    """Packed date of every day from STARTDATE to past the last receipt date."""
    days = _DAY0 + np.arange(LAST_ORDER_DAY + 121 + 30 + 1)
    y = days.astype("datetime64[Y]").astype(int) + 1970
    m = days.astype("datetime64[M]").astype(int) % 12 + 1
    d = (days - days.astype("datetime64[M]")).astype(int) + 1
    return ((y * 13 + m) * 32 + d).astype(np.int64) * US_DAY


PACKED = _packed_days()

# ---------------------------------------------------------------- text (4.2.2.10)

_NOUNS = ("foxes ideas theodolites pinto_beans instructions dependencies excuses platelets asymptotes "
          "courts dolphins multipliers sauternes warthogs frets dinos attainments somas Tiresias' "
          "patterns forges braids hockey_players frays warhorses dugouts notornis epitaphs pearls "
          "tithes waters orbits gifts sheaves depths sentiments decoys realms pains grouches escapades")
_VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate maintain nod was lose "
          "sublate solve thrash promise engage hinder print x-ray breach eat grow impress mold poach "
          "serve run dazzle snooze doze unwind kindle play hang believe doubt")
_ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged daring "
               "brave stealthy permanent enticing idle busy regular final ironic even bold silent")
_ADVERBS = ("sometimes always never furiously slyly carefully blithely quickly fluffily slowly quietly "
            "ruthlessly thinly closely doggedly daringly bravely stealthily permanently enticingly idly "
            "busily regularly finally ironically evenly boldly silently")
_PREPOSITIONS = ("about above according_to across after against along alongside_of among around at atop "
                 "before behind beneath beside besides between beyond by despite during except for from "
                 "in_place_of inside instead_of into near of on outside over past since through "
                 "throughout to toward under until up upon without with within")
_AUXILIARIES = ("do may might shall will would can could should ought_to must will_have_to "
                "shall_have_to could_have_to should_have_to must_have_to need_to try_to")
_TERMINATORS = (".", ";", ":", "?", "!", "--")
POOL_BYTES = 1 << 20
_pool_cache: list[np.ndarray] = []


def _words(text: str) -> list[str]:
    return [w.replace("_", " ") for w in text.split()]


def text_pool() -> np.ndarray:
    """1 MiB of the grammar's sentences, the same in every run; a
    comment is a substring of it (dbgen: of its 300 MB pool)."""
    if _pool_cache:
        return _pool_cache[0]
    r = random.Random(19980401)
    nouns, verbs, adjs, advs = _words(_NOUNS), _words(_VERBS), _words(_ADJECTIVES), _words(_ADVERBS)
    preps, auxs = _words(_PREPOSITIONS), _words(_AUXILIARIES)

    def noun_phrase():
        return r.choice((
            lambda: r.choice(nouns),
            lambda: f"{r.choice(adjs)} {r.choice(nouns)}",
            lambda: f"{r.choice(adjs)}, {r.choice(adjs)} {r.choice(nouns)}",
            lambda: f"{r.choice(advs)} {r.choice(adjs)} {r.choice(nouns)}"))()

    def verb_phrase():
        return r.choice((
            lambda: r.choice(verbs),
            lambda: f"{r.choice(auxs)} {r.choice(verbs)}",
            lambda: f"{r.choice(verbs)} {r.choice(advs)}",
            lambda: f"{r.choice(auxs)} {r.choice(verbs)} {r.choice(advs)}"))()

    def prep_phrase():
        return f"{r.choice(preps)} the {noun_phrase()}"

    def sentence():
        return r.choice((
            lambda: f"{noun_phrase()} {verb_phrase()}",
            lambda: f"{noun_phrase()} {verb_phrase()} {prep_phrase()}",
            lambda: f"{noun_phrase()} {verb_phrase()} {noun_phrase()}",
            lambda: f"{noun_phrase()} {prep_phrase()} {verb_phrase()} {noun_phrase()}",
            lambda: f"{noun_phrase()} {prep_phrase()} {verb_phrase()} {prep_phrase()}"))() + r.choice(_TERMINATORS)

    parts, size = [], 0
    while size < POOL_BYTES:
        s = sentence() + " "
        parts.append(s)
        size += len(s)
    _pool_cache.append(np.frombuffer("".join(parts)[:POOL_BYTES].encode("ascii"), dtype=np.uint8))
    return _pool_cache[0]


def _cut(matrix: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Rows of bytes cut to their lengths, as one `S<width>` lane."""
    width = matrix.shape[1]
    matrix[np.arange(width, dtype=np.int32) >= lengths[:, None]] = 0
    return np.ascontiguousarray(matrix).view(f"S{width}").ravel()


def text(rng, n: int, lo: int, hi: int, chunk: int = 1 << 20) -> np.ndarray:
    """n text strings of lo..hi characters."""
    windows = np.lib.stride_tricks.sliding_window_view(text_pool(), hi)
    out = np.empty(n, dtype=f"S{hi}")
    for a in range(0, n, chunk):
        m = min(chunk, n - a)
        rows = windows[rng.integers(0, len(windows), m)]  # a copy, m x hi
        out[a:a + m] = _cut(rows, rng.integers(lo, hi + 1, m, dtype=np.int32))
    return out


_ALNUM = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ,. ", dtype=np.uint8)


def v_string(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """Random v-strings (4.2.2.7): lo..hi characters of a 65-character alphabet."""
    return _cut(_ALNUM[rng.integers(0, len(_ALNUM), (n, hi))], rng.integers(lo, hi + 1, n, dtype=np.int32))


def numbered(prefix: bytes, numbers: np.ndarray, digits: int = 9) -> np.ndarray:
    """`Customer#000000001`, `Clerk#000000951`."""
    out = np.empty((len(numbers), len(prefix) + digits), dtype=np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix, dtype=np.uint8)
    out[:, len(prefix):] = numbers[:, None] // 10 ** np.arange(digits - 1, -1, -1) % 10 + ord("0")
    return out.view(f"S{out.shape[1]}").ravel()


# ---------------------------------------------------------------- the tables


def line_counts(n_orders: int, n_lineitem: int) -> np.ndarray:
    """Lineitems of each order: 1..7 in turn, the few steps to the exact
    total spread over the first orders, then permuted: the same
    permutation for every seed (see the module's text)."""
    counts = np.arange(n_orders, dtype=np.int64) % 7 + 1
    diff = n_lineitem - int(counts.sum())
    room = np.flatnonzero(counts < 7) if diff > 0 else np.flatnonzero(counts > 1)
    if abs(diff) > len(room):
        raise ValueError(f"{n_lineitem} lineitems cannot be spread over {n_orders} orders at 1..7 each")
    counts[room[:abs(diff)]] += 1 if diff > 0 else -1
    return np.random.default_rng([n_orders, n_lineitem, 0x7C9]).permutation(counts)


_made: dict = {}  # (seed, sizes) -> the tables of the last seed asked for


def _tables(seed: int, n_lineitem: int, n_orders: int, n_customer: int) -> dict:
    key = (seed, n_lineitem, n_orders, n_customer)
    if key in _made:
        return _made[key]
    _made.clear()
    rng = np.random.default_rng([seed, 0x7C9])
    sf = n_orders / 1_500_000
    n_part, n_supp, n_clerk = max(int(sf * 200_000), 1), max(int(sf * 10_000), 1), max(int(sf * 1_000), 1)

    # ORDERS, but for what the lineitems decide
    i = np.arange(n_orders, dtype=np.int64)
    o_orderkey = i // 8 * 32 + i % 8 + 1
    j = rng.integers(0, n_customer - n_customer // 3, n_orders)
    o_custkey = j + j // 2 + 1  # 1, 2, 4, 5, 7, ...: never a multiple of 3
    o_day = rng.integers(0, LAST_ORDER_DAY + 1, n_orders)

    # LINEITEM
    counts = line_counts(n_orders, n_lineitem)
    first = np.concatenate(([0], np.cumsum(counts)[:-1]))
    of_order = np.repeat(i, counts)
    partkey = rng.integers(1, n_part + 1, n_lineitem)
    suppkey = (partkey + rng.integers(0, 4, n_lineitem) * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    qty = rng.integers(1, 51, n_lineitem)
    price = qty * (90000 + partkey // 10 % 20001 + 100 * (partkey % 1000))  # quantity x p_retailprice
    discount = rng.integers(0, 11, n_lineitem)
    tax = rng.integers(0, 9, n_lineitem)
    ordered = o_day[of_order]
    ship = ordered + rng.integers(1, 122, n_lineitem)
    commit = ordered + rng.integers(30, 91, n_lineitem)
    receipt = ship + rng.integers(1, 31, n_lineitem)
    returned = np.where(rng.random(n_lineitem) < 0.5, 0, 2)  # A or R
    open_line = ship > CURRENT_DAY
    lineitem = {
        "l_orderkey": o_orderkey[of_order],
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": np.arange(n_lineitem, dtype=np.int64) - first[of_order] + 1,
        "l_quantity": qty * 100,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": RETURNFLAGS[np.where(receipt <= CURRENT_DAY, returned, 1)],
        "l_linestatus": LINESTATUS[open_line.astype(np.int8)],
        "l_shipdate": PACKED[ship],
        "l_commitdate": PACKED[commit],
        "l_receiptdate": PACKED[receipt],
        "l_shipinstruct": INSTRUCTIONS[rng.integers(0, 4, n_lineitem)],
        "l_shipmode": MODES[rng.integers(0, 7, n_lineitem)],
        "l_comment": text(rng, n_lineitem, 10, 43),
    }

    n_open = np.add.reduceat(open_line.astype(np.int64), first)
    charge = np.add.reduceat(price * (100 + tax) * (100 - discount), first)  # scale 6
    orders = {
        "o_orderkey": o_orderkey,
        "o_custkey": o_custkey,
        "o_orderstatus": ORDERSTATUS[np.where(n_open == 0, 0, np.where(n_open == counts, 1, 2))],
        "o_totalprice": (charge + 5000) // 10000,  # to cents, half up
        "o_orderdate": PACKED[o_day],
        "o_orderpriority": PRIORITIES[rng.integers(0, 5, n_orders)],
        "o_clerk": numbered(b"Clerk#", rng.integers(1, n_clerk + 1, n_orders)),
        "o_shippriority": np.zeros(n_orders, dtype=np.int64),
        "o_comment": text(rng, n_orders, 19, 78),
    }

    c_custkey = np.arange(1, n_customer + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n_customer)
    phone = np.empty((n_customer, 15), dtype=np.uint8)
    phone[:] = ord("-")
    for at, (number, digits) in {0: (nation + 10, 2), 3: (rng.integers(100, 1000, n_customer), 3),
                                 7: (rng.integers(100, 1000, n_customer), 3),
                                 11: (rng.integers(1000, 10000, n_customer), 4)}.items():
        phone[:, at:at + digits] = number[:, None] // 10 ** np.arange(digits - 1, -1, -1) % 10 + ord("0")
    customer = {
        "c_custkey": c_custkey,
        "c_name": numbered(b"Customer#", c_custkey),
        "c_address": v_string(rng, n_customer, 10, 40),
        "c_nationkey": nation,
        "c_phone": phone.view("S15").ravel(),
        "c_acctbal": rng.integers(-99999, 1000000, n_customer),
        "c_mktsegment": SEGMENTS[rng.integers(0, 5, n_customer)],
        "c_comment": text(rng, n_customer, 29, 116),
    }
    _made[key] = {"lineitem": lineitem, "orders": orders, "customer": customer}
    return _made[key]


def _sizes(lineitem: int, orders: int | None = None, customer: int | None = None, **_others) -> tuple:
    """A configuration without ORDERS or CUSTOMER still has the orders its
    lineitems belong to: at TPC-H's ratios, 4 lineitems an order, 10 orders a customer."""
    orders = orders or max(lineitem // 4, 2)
    return lineitem, orders, customer or max(orders // 10, 2)


def lineitem(n_rows: int, seed: int, **sizes) -> dict[str, np.ndarray]:
    return _tables(seed, *_sizes(**sizes))["lineitem"]


def orders(n_rows: int, seed: int, **sizes) -> dict[str, np.ndarray]:
    return _tables(seed, *_sizes(**sizes))["orders"]


def customer(n_rows: int, seed: int, **sizes) -> dict[str, np.ndarray]:
    return _tables(seed, *_sizes(**sizes))["customer"]
