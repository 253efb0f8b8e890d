"""Shared arithmetic of the plain references: exact scaled-integer
decimals rendered as MySQL renders them, and the comparison of a top-k
answer whose text leaves ties open."""

from __future__ import annotations

from decimal import ROUND_HALF_UP, Decimal

PRECISIONS = ("exact", "float32")


def dec_text(scaled: int, scale: int) -> str:
    """A scaled integer as DECIMAL text with `scale` fraction digits."""
    return str(Decimal(int(scaled)).scaleb(-scale))


def avg_text(total_scaled: int, count: int, scale: int) -> str:
    """AVG of a DECIMAL(.., scale): MySQL adds div_precision_increment = 4
    digits and rounds half away from zero."""
    q = Decimal(1).scaleb(-(scale + 4))
    return str((Decimal(int(total_scaled)).scaleb(-scale) / Decimal(int(count))).quantize(q, ROUND_HALF_UP))


def isum(values, precision: str) -> int:
    """Sum of a vector of scaled integers: exact in int64, or — the
    control — accumulated in float32 like a lower-precision kernel."""
    if precision == "exact":
        return int(values.sum())
    return int(values.astype("float32").sum(dtype="float32"))


def compare_topk(rows: list[tuple], want: dict) -> str | None:
    """`want`: {"keys": the sort keys of the k winners in order (one
    tuple of texts a row), "members": {row tuple, ...} every row that may
    stand at a position holding its key, "key_cols": where the key's
    parts stand in a row}. Where the text's ORDER BY leaves ties open,
    rows that tie on the key may come in any order and any of the tied
    rows may take the last places; the key sequence itself is exact,
    every row has to exist, none twice."""
    kc = want["key_cols"]
    if len(rows) != len(want["keys"]):
        return f"{len(rows)} rows, want {len(want['keys'])}"
    if [tuple(r[c] for c in kc) for r in rows] != want["keys"]:
        return "sort keys differ"
    if len(set(rows)) != len(rows):
        return "a row twice"
    for r in rows:
        if r not in want["members"]:
            return f"row {r} is no row of the answer"
    return None
