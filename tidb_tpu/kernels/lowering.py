"""SQL expressions onto device lanes.

Strings never reach the device: an object lane dict-encodes to sorted-
vocab int32 codes (`dict_encode_lane`), and `rewrite` maps every
comparison of such a column with a string constant through the vocab, so
code order == collation order and eq/range/IN predicates are exact in
code space. `eval_device` then evaluates the rewritten tree over lanes
(jnp arrays inside a program; numpy lanes for the MPP engine's host-side
pushed selection), and `selection_mask` ANDs a condition list into the
row mask.
"""

from __future__ import annotations

import bisect

import numpy as np

from ..jaxenv import jax, jnp
from ..expr.expression import Column as ExprCol, Constant, Expression, ScalarFunc, make_func
from ..mysqltypes import collate as _coll
from ..mysqltypes.datum import Datum, K_STR, K_BYTES
from ..mysqltypes.field_type import ft_longlong

_CMP_SWAP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}


class Vocab(list):
    """Sorted dict-encode vocabulary: ORIGINAL values in code order, plus
    the lookup keys codes were assigned by (weight strings under a ci
    collation, the values themselves under binary)."""

    def __init__(self, originals, keys=None, coll="utf8mb4_bin"):
        super().__init__(originals)
        self.keys = list(self) if keys is None else keys
        self.coll = coll

    def lookup(self, s: str):
        """(insertion position, exact-present) for a constant under this
        vocab's collation — the bisect behind code-space compare/IN."""
        k = _coll.weight(s, self.coll) if _coll.is_ci(self.coll) else s
        i = bisect.bisect_left(self.keys, k)
        return i, i < len(self.keys) and self.keys[i] == k


def dict_encode_lane(d: np.ndarray, v: np.ndarray, coll: str = "utf8mb4_bin"):
    """Vectorized sorted-dict encoding of an object lane → (int32 codes,
    Vocab). Handles str lanes (numpy 'U' fast path) and bytes lanes
    (latin-1 view: byte order == code-point order, so code order stays
    binary-collation order); mixed lanes take the generic python path.
    Under a ci collation codes follow WEIGHT order — equal-weight values
    share one code whose vocab entry is the binary-min original (the same
    representative the host paths resolve ties to)."""
    if not v.any():
        return np.zeros(len(d), np.int32), Vocab([], coll=coll)
    present = d[v]
    kinds = {type(x) for x in present.tolist()}
    if _coll.is_ci(coll) and kinds <= {str}:
        raw = np.where(v, d, "")
        wa = _coll.weight_lane(raw, coll).astype("U")
        sel = np.nonzero(v)[0]
        # representative per weight class = FIRST occurrence in row order,
        # matching the host engines' first-row group output and the
        # first-wins tie rule of min/max
        uniqw, first = np.unique(wa[sel], return_index=True)
        reps = [d[i] for i in sel[first]]
        codes = np.searchsorted(uniqw, wa).astype(np.int32)
        codes[~v] = 0
        return codes, Vocab(reps, keys=uniqw.tolist(), coll=coll)
    if kinds <= {str}:
        vals = np.where(v, d, "").astype("U")
        vocab_arr = np.unique(vals[v])
        codes = np.searchsorted(vocab_arr, vals).astype(np.int32)
        codes[~v] = 0
        return codes, Vocab(vocab_arr.tolist())
    if kinds <= {bytes}:
        as_str = np.array([x.decode("latin-1") for x in present.tolist()], dtype="U")
        vocab_arr = np.unique(as_str)
        codes = np.zeros(len(d), np.int32)
        codes[v] = np.searchsorted(vocab_arr, as_str).astype(np.int32)
        orig = [s.encode("latin-1") for s in vocab_arr.tolist()]
        return codes, Vocab(orig, keys=vocab_arr.tolist())
    # mixed str/bytes/other: generic exact path
    vocab = sorted({x if isinstance(x, str) else x.decode("latin-1") for x in present.tolist()})
    code_of = {s: i for i, s in enumerate(vocab)}
    codes = np.zeros(len(d), np.int32)
    for i in np.nonzero(v)[0]:
        x = d[i]
        codes[i] = code_of[x if isinstance(x, str) else x.decode("latin-1")]
    return codes, Vocab(vocab)


def rewrite(e: Expression, vocabs: dict[int, Vocab]):
    """Rewrite an expression into device (code-space) form; None if not
    lowerable. String columns become int32 code lanes; comparisons with
    string constants map through the sorted vocab so code order ==
    collation order."""
    if isinstance(e, ExprCol):
        return e  # codes lane supplied by caller keyed on idx
    if isinstance(e, Constant):
        if e.value.kind in (K_STR, K_BYTES):
            return None  # bare string const outside rewritten cmp
        return e
    if not isinstance(e, ScalarFunc):
        return None
    name = e.sig.name
    # comparison with a string column vs string constant
    if name in _CMP_SWAP and len(e.args) == 2:
        a, b = e.args
        if isinstance(b, ExprCol) and isinstance(a, Constant):
            a, b = b, a
            name = _CMP_SWAP[name]
        if isinstance(a, ExprCol) and a.idx in vocabs and isinstance(b, Constant):
            if b.value.kind not in (K_STR, K_BYTES):
                return None
            return code_cmp(name, a, b, vocabs[a.idx])
        if isinstance(a, ExprCol) and a.idx in vocabs:
            return None  # string col vs non-const: host
    if name == "in" and isinstance(e.args[0], ExprCol) and e.args[0].idx in vocabs:
        vocab = vocabs[e.args[0].idx]
        codes = []
        for c in e.args[1:]:
            if not isinstance(c, Constant) or c.value.kind not in (K_STR, K_BYTES):
                return None
            i, present = vocab.lookup(c.value.to_str())
            codes.append(i if present else -1)
        col = ExprCol(e.args[0].idx, ft_longlong(), e.args[0].name)
        return make_func("in", col, *[Constant(Datum.i(c), ft_longlong()) for c in codes])
    # strings in any other position: not lowerable
    for a in e.args:
        if isinstance(a, ExprCol) and a.idx in vocabs:
            return None
    new_args = [rewrite(a, vocabs) for a in e.args]
    if any(a is None for a in new_args):
        return None
    return ScalarFunc(e.sig, new_args, e.ret_type)


def code_cmp(op: str, col: ExprCol, const: Constant, vocab: Vocab):
    """col <op> 'str' → code-space comparison via sorted-vocab bisect
    (weight-space under a ci collation)."""
    pos, present = vocab.lookup(const.value.to_str())
    icol = ExprCol(col.idx, ft_longlong(), col.name)

    def c(v):
        return Constant(Datum.i(v), ft_longlong())

    if op == "eq":
        return make_func("eq", icol, c(pos if present else -1))
    if op == "ne":
        return make_func("ne", icol, c(pos if present else -1))
    if op == "lt":
        return make_func("lt", icol, c(pos))
    if op == "ge":
        return make_func("ge", icol, c(pos))
    if op == "le":
        return make_func("lt" if not present else "le", icol, c(pos))
    if op == "gt":
        return make_func("ge" if not present else "gt", icol, c(pos))
    return None


def eval_device(e: Expression, lanes: dict):
    """Recursive device eval of a rewritten expression over lanes keyed
    by column index → (data, valid)."""

    def rec(x):
        if isinstance(x, ExprCol):
            return lanes[x.idx]
        if isinstance(x, Constant):
            v = x.scalar_value()
            if v is None:
                z = jnp.zeros((), dtype=jnp.int64)
                return z, jnp.zeros((), dtype=bool)
            if x.ret_type.is_float():
                dt = jnp.float64
            elif isinstance(v, int) and v > np.iinfo(np.int64).max:
                dt = jnp.uint64  # literals above 2^63-1 (BIGINT UNSIGNED)
            else:
                dt = jnp.int64
            return jnp.asarray(v, dtype=dt), jnp.asarray(True)
        avals = [rec(a) for a in x.args]
        return x.eval_xp(jnp, avals)

    return rec(e)


def eval_flat(e: Expression, lanes: dict, shape):
    """`eval_device` as flat (data, valid) lanes of `shape`, a constant
    broadcast to it."""
    d, v = eval_device(e, lanes)
    d = jnp.full(shape, d) if d.ndim == 0 else d.reshape(-1)
    v = jnp.full(shape, v) if v.ndim == 0 else v.reshape(-1)
    return d, v


def and_conds(r_conds, lanes, mask):
    """`mask` AND every condition that is neither NULL nor false (a
    constant condition broadcasts against the mask)."""
    for c in r_conds:
        d, v = eval_device(c, lanes)
        mask = mask & v & (d != 0)
    return mask


def selection_mask(r_conds, lanes, row_valid):
    """The cop programs' pushed Selection, under the `sel` scope."""
    with jax.named_scope("sel"):
        return and_conds(r_conds, lanes, row_valid)
