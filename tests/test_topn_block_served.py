"""A served TopN at a size where `kernels.top_k` takes its block-pruned
form (PR 33): one table of 2^17 rows in one bucket, many rows sharing
the k-th key. `tidb_cop_engine = 'tpu'` answers the key sequence the
host engine answers, every row a row of the table, none twice: which of
the tied rows are answered is either engine's choice, that they are rows
of the table with the right keys is not.
"""

import numpy as np
import pytest

ROWS = 1 << 17
LIMIT = 100


@pytest.fixture(scope="module")
def served():
    from tidb_tpu.br.ingest import BulkIngest
    from tidb_tpu.session import Session

    s = Session()
    s.execute("CREATE TABLE tk (id BIGINT NOT NULL, k BIGINT, d DECIMAL(12,2) NOT NULL, f BIGINT NOT NULL)")
    rng = np.random.default_rng(33)
    cols = {
        "id": np.arange(ROWS, dtype=np.int64),
        "k": rng.integers(0, 40, ROWS),  # some 3,300 rows a value
        "d": rng.integers(0, 200_000, ROWS),  # scaled DECIMAL(12,2): 0.00 to 1999.99
        "f": rng.integers(0, 1000, ROWS),  # the selection's column
    }
    k_valid = rng.random(ROWS) > 0.01  # some 1,300 NULL keys: more than LIMIT
    table = {"id": cols["id"].copy(), "k": np.where(k_valid, cols["k"], -1),
             "d": cols["d"].copy(), "f": cols["f"].copy()}
    job = BulkIngest(s, s.infoschema().table(s.current_db, "tk"))
    job.add_columns(list(cols), list(cols.values()), valids=[None, k_valid, None, None])
    job.commit()
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.store.timeline.resize(1 << 14)
    return s, table


def _run(s, engine, sql):
    s.vars["tidb_cop_engine"] = engine
    ring = s.store.timeline
    ring.clear()
    rows = s.must_query(sql)
    return rows, [e for e in ring.snapshot() if e.name == "cop.lower"]


CASES = {
    # key column, scale, statement; the key is the second output column
    "desc": ("k", 0, "SELECT id, k FROM tk ORDER BY k DESC LIMIT 100"),
    "asc_nulls_first": ("k", 0, "SELECT id, k FROM tk ORDER BY k LIMIT 100"),
    "decimal_desc": ("d", 2, "SELECT id, d FROM tk ORDER BY d DESC LIMIT 100"),
    "decimal_asc": ("d", 2, "SELECT id, d FROM tk ORDER BY d LIMIT 100"),
    "masks_most_rows_desc": ("k", 0, "SELECT id, k FROM tk WHERE f < 5 ORDER BY k DESC LIMIT 100"),
    "masks_most_rows_asc": ("k", 0, "SELECT id, k FROM tk WHERE f < 5 ORDER BY k LIMIT 100"),
    "masks_most_rows_decimal": ("d", 2, "SELECT id, d FROM tk WHERE f < 5 ORDER BY d DESC LIMIT 100"),
    "fewer_than_limit_pass": ("k", 0, "SELECT id, k FROM tk WHERE f = 0 AND k > 37 ORDER BY k DESC LIMIT 100"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_topn_block_form_matches_host(served, case):
    from tidb_tpu.kernels.primitives import topk_blocks

    s, table = served
    col, scale, sql = CASES[case]
    host, _ = _run(s, "host", sql)
    fallbacks = s.cop.tpu.fallbacks
    got, lowers = _run(s, "tpu", sql)
    assert s.cop.tpu.fallbacks == fallbacks
    # the device ran it, in the pruned form: one bucket of 2^17 positions
    assert [e.args.get("topk_blk") for e in lowers] == [topk_blocks(ROWS, LIMIT)] == [128]

    def key(v):
        return None if v is None else int(round(float(v) * 10**scale))

    assert [key(r[1]) for r in got] == [key(r[1]) for r in host]  # the key sequence
    assert len(got) == (LIMIT if case != "fewer_than_limit_pass" else len(host)) > 0
    ids = [int(r[0]) for r in got]
    assert len(set(ids)) == len(ids)  # none twice
    for i, r in zip(ids, got):  # every row a row of the table
        want = int(table[col][i])
        assert key(r[1]) == (None if col == "k" and want == -1 else want)
    if "masks_most_rows" in case or case == "fewer_than_limit_pass":
        assert all(table["f"][i] < 5 for i in ids)


def test_cases_cut_through_ties(served):
    """The data makes what the file claims: more NULL keys than LIMIT
    (ascending, the cut falls among them), thousands of rows at the
    largest key, a selection that keeps under a hundredth of the rows."""
    _, table = served
    assert (table["k"] == -1).sum() > LIMIT
    assert (table["k"] == 39).sum() > 10 * LIMIT
    assert LIMIT < (table["f"] < 5).sum() < ROWS // 100
    assert 0 < ((table["f"] == 0) & (table["k"] > 37)).sum() < LIMIT
