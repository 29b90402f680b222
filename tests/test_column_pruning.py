"""Column pruning (plan/planner.py:_ColumnReads): a query body's table
readers ask for the columns its statement names and no others, and the
answers are what whole-row readers give.

Every case is one SQL text run twice on the same data: with the rule,
and with `_ColumnReads.of` patched to keep every column (the planner
has no switch for it; the plan cache is keyed by the text, so the second
run carries a trailing comment). The benchmark's statements run on
TPC-H's full-width tables as its generator declares and fills them, and
their readers must ask for exactly the `needed_columns` their files
list, table by table.
"""

import importlib.util
import json
from pathlib import Path

import pytest

from tidb_tpu.plan import physical as ph
from tidb_tpu.plan import planner
from tidb_tpu.session import Session
from tidb_tpu.store import new_mock_storage

BENCH = Path(__file__).parent.parent / "benchmark"
TPCH_TABLES = ("region", "nation", "supplier", "customer", "orders",
               "lineitem")


def _generator():
    spec = importlib.util.spec_from_file_location(
        "_tpch_dbgen", BENCH / "generators" / "tpch_dbgen.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _statement(name):
    return json.loads((BENCH / "statements" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def tk():
    from tidb_tpu.table import Table, bulkload
    storage = new_mock_storage()
    storage.async_commit_secondaries = False
    s = Session(storage)
    s.execute("CREATE DATABASE tpch; USE tpch")
    gen = _generator()
    data = gen.generate({"sf": 0.002}, 20261004)
    for t in TPCH_TABLES:
        s.execute(gen.ddl(t))
        table = Table(s.domain.info_schema().table("tpch", t), storage)
        bulkload.bulk_load(storage, table, gen.columns(data, t),
                           handles=gen.handles(t, data))
    s.execute("CREATE DATABASE test; USE test")
    s.execute("CREATE TABLE t (a BIGINT PRIMARY KEY, b INT, c DOUBLE, "
              "d VARCHAR(20), e DECIMAL(10,2), f DATE, KEY ib (b), "
              "KEY ibd (b, d))")
    s.execute("INSERT INTO t VALUES "
              "(1, 10, 1.5, 'x', 1.25, '2024-01-01'), "
              "(2, 20, 2.5, 'y', 2.50, '2024-01-02'), "
              "(3, 30, 3.5, NULL, 3.75, '2024-01-03'), "
              "(4, NULL, 4.5, 'x', NULL, NULL), "
              "(5, 20, 5.5, 'z', 5.00, '2024-01-05')")
    s.execute("CREATE TABLE u (a BIGINT PRIMARY KEY, b INT, g VARCHAR(8), "
              "h INT)")
    s.execute("INSERT INTO u VALUES (1, 10, 'p', 7), (2, 21, 'q', 8), "
              "(5, 20, 'r', NULL), (6, 60, 's', 9)")
    # w: a row written before each ADD COLUMN reads the default
    s.execute("CREATE TABLE w (k BIGINT PRIMARY KEY, v INT)")
    s.execute("INSERT INTO w VALUES (1, 100), (2, 200)")
    s.execute("ALTER TABLE w ADD COLUMN n INT DEFAULT 42")
    s.execute("ALTER TABLE w ADD COLUMN m VARCHAR(8) DEFAULT 'dflt'")
    s.execute("INSERT INTO w VALUES (3, 300, 7, 'own')")
    yield s
    s.close()
    storage.close()


def _readers(plan):
    """[(table, [column names], node)] of every reader under `plan`, an
    Apply's inner plan and a DML statement's reader included."""
    out = []
    for _depth, node in plan.explain_nodes():
        if isinstance(node, ph.PhysIndexLookUp):
            out.append((node.table_cop.table.name.lower(),
                        [c.name.lower() for c in node.table_cop.cols], node))
        elif isinstance(node, ph.PhysPointGet):
            out.append((node.table.name.lower(),
                        [c.name.lower() for c in node.cols], node))
        elif isinstance(node, (ph.PhysTableReader, ph.PhysIndexReader)):
            out.append((node.cop.table.name.lower(),
                        [c.name.lower() for c in node.cop.cols], node))
    return out


def _both(tk, monkeypatch, sql, db="test"):
    """-> (rows with the rule, rows with whole-row readers, both sorted,
    and the pruned plan's readers)."""
    tk.execute(f"USE {db}")
    got = tk.query(sql).rows
    readers = _readers(tk.plan(sql))
    with monkeypatch.context() as m:
        m.setattr(planner._ColumnReads, "of",
                  lambda self, ref, info: info.public_columns())
        want = tk.query(sql + " /* whole rows */").rows
        whole = _readers(tk.plan(sql))
    assert all(len(cols) == len(node_cols(n)) for _t, cols, n in whole), whole
    return sorted(got, key=repr), sorted(want, key=repr), readers


def node_cols(node):
    if isinstance(node, ph.PhysPointGet):
        return node.table.public_columns()
    cop = node.table_cop if isinstance(node, ph.PhysIndexLookUp) else node.cop
    return cop.table.public_columns()


def _cols_by_table(readers):
    out = {}
    for table, cols, _n in readers:
        out.setdefault(table, []).append(sorted(cols))
    return out


Q18_LOW = _statement("q18")["sql"].replace("> 300", "> 180")

# (id, db, sql, {table: columns every reader of it asks for} or None)
SELECT_CASES = [
    ("star", "test", "SELECT * FROM t WHERE b >= 20",
     {"t": ["a", "b", "c", "d", "e", "f"]}),
    ("qualified_star", "test",
     "SELECT t.*, u.g FROM t JOIN u ON t.a = u.a",
     {"t": ["a", "b", "c", "d", "e", "f"], "u": ["a", "g"]}),
    ("star_of_derived_table", "test",
     "SELECT * FROM (SELECT a, d FROM t WHERE b > 10) x",
     {"t": ["a", "b", "d"]}),
    ("using", "test",
     "SELECT a, t.c, u.g FROM t JOIN u USING (a)",
     {"t": ["a", "c"], "u": ["a", "g"]}),
    ("using_star", "test", "SELECT * FROM t JOIN u USING (a, b)",
     {"t": ["a", "b", "c", "d", "e", "f"], "u": ["a", "b", "g", "h"]}),
    ("natural", "test", "SELECT t.c, u.g FROM t NATURAL JOIN u",
     {"t": ["a", "b", "c", "d", "e", "f"], "u": ["a", "b", "g", "h"]}),
    ("left_join_null_side", "test",
     "SELECT t.a, u.g FROM t LEFT JOIN u ON t.a = u.a AND u.h > 7",
     {"t": ["a"], "u": ["a", "g", "h"]}),
    ("order_by_unselected", "test", "SELECT d FROM t ORDER BY c DESC",
     {"t": ["c", "d"]}),
    ("order_by_position_and_alias", "test",
     "SELECT e AS price, d FROM t ORDER BY 2, price",
     {"t": ["d", "e"]}),
    ("having", "test",
     "SELECT d, SUM(c) FROM t GROUP BY d HAVING MAX(b) > 10",
     {"t": ["b", "c", "d"]}),
    ("having_without_agg", "test", "SELECT a, b AS bb FROM t HAVING bb > 10",
     {"t": ["a", "b"]}),
    ("count_star", "test", "SELECT COUNT(*) FROM t", {"t": ["a"]}),
    ("constant_rows", "test", "SELECT 1 FROM u WHERE 1 = 1", {"u": ["a"]}),
    ("distinct", "test", "SELECT DISTINCT d FROM t", {"t": ["d"]}),
    ("correlated_exists", "test",
     "SELECT t.d FROM t WHERE EXISTS "
     "(SELECT 1 FROM u WHERE u.b = t.b AND u.h > 6)",
     {"t": ["b", "d"], "u": ["b", "h"]}),
    ("correlated_scalar", "test",
     "SELECT t.a, (SELECT MAX(u.h) FROM u WHERE u.a <= t.a) FROM t",
     {"t": ["a"], "u": ["a", "h"]}),
    ("correlated_apply_not_decorrelated", "test",
     "SELECT a FROM t WHERE c > (SELECT COUNT(*) FROM u WHERE u.b < t.b)",
     {"t": ["a", "b", "c"], "u": ["b"]}),
    ("in_subquery_grouped", "test",
     "SELECT d FROM t WHERE b IN "
     "(SELECT b FROM u GROUP BY b HAVING SUM(h) > 7)",
     {"t": ["b", "d"], "u": ["b", "h"]}),
    ("not_in", "test", "SELECT a FROM t WHERE b NOT IN (SELECT h FROM u)",
     {"t": ["a", "b"], "u": ["h"]}),
    ("derived_table", "test",
     "SELECT x.s FROM (SELECT d, SUM(e) AS s FROM t GROUP BY d) x "
     "WHERE x.s > 1",
     {"t": ["d", "e"]}),
    ("derived_join", "test",
     "SELECT x.b, u.g FROM (SELECT b, c FROM t) x JOIN u ON x.b = u.b",
     {"t": ["b", "c"], "u": ["b", "g"]}),
    ("union", "test",
     "SELECT a, d FROM t WHERE b > 10 UNION SELECT a, g FROM u "
     "ORDER BY 1",
     {"t": ["a", "b", "d"], "u": ["a", "g"]}),
    ("union_all_limit", "test",
     "SELECT b FROM t UNION ALL SELECT h FROM u ORDER BY b LIMIT 4",
     {"t": ["b"], "u": ["h"]}),
    ("self_join_aliases", "test",
     "SELECT x.d, y.c FROM t x JOIN t y ON x.b = y.b WHERE y.a > 1",
     {"t": None}),
    ("case_and_functions", "test",
     "SELECT CASE WHEN b > 10 THEN d ELSE 'low' END, "
     "YEAR(f), IFNULL(e, 0) FROM t",
     {"t": ["b", "d", "e", "f"]}),
    ("alter_add_column_default", "test",
     "SELECT k, n FROM w ORDER BY k", {"w": ["k", "n"]}),
    ("alter_add_column_string_default", "test",
     "SELECT m FROM w WHERE n = 42 ORDER BY k", {"w": ["k", "m", "n"]}),
    ("alter_added_column_not_read", "test",
     "SELECT SUM(v) FROM w", {"w": ["v"]}),
    ("point_get", "test", "SELECT d FROM t WHERE a = 3", {"t": ["a", "d"]}),
    ("index_lookup", "test",
     "SELECT c FROM t USE INDEX (ib) WHERE b = 20", {"t": ["b", "c"]}),
    ("q18_with_rows", "tpch", Q18_LOW, _statement("q18")["needed_columns"]),
] + [
    (name, "tpch", _statement(name)["sql"],
     _statement(name)["needed_columns"])
    for name in ("q1", "q3", "q5", "q18")]


@pytest.mark.parametrize("db,sql,want_cols", [c[1:] for c in SELECT_CASES],
                         ids=[c[0] for c in SELECT_CASES])
def test_select_reads_named_columns_and_answers_alike(
        tk, monkeypatch, db, sql, want_cols):
    ordered = "order by" in sql.lower() and "limit" not in sql.lower()
    got, want, readers = _both(tk, monkeypatch, sql, db)
    assert got == want
    if ordered:
        assert tk.query(sql).rows == tk.query(sql + " /* whole rows */").rows
    by_table = _cols_by_table(readers)
    assert set(by_table) == set(want_cols)
    for table, cols in want_cols.items():
        if cols is not None:
            for asked in by_table[table]:
                assert asked == sorted(cols), (table, asked)


def test_benchmark_statements_read_rows(tk):
    """The equality above is not of two empty answers."""
    tk.execute("USE tpch")
    for sql in (Q18_LOW, _statement("q1")["sql"], _statement("q3")["sql"],
                _statement("q5")["sql"]):
        assert tk.query(sql).rows


def test_self_join_prunes_each_alias_by_its_own_qualifier(tk, monkeypatch):
    _g, _w, readers = _both(
        tk, monkeypatch,
        "SELECT x.d, y.c FROM t x JOIN t y ON x.b = y.b WHERE y.a > 1")
    assert sorted(sorted(cols) for _t, cols, _n in readers) == \
        [["a", "b", "c"], ["b", "d"]]


def test_q18_inner_reader_is_pruned_too(tk):
    tk.execute("USE tpch")
    plan = tk.plan(_statement("q18")["sql"])
    line = [cols for table, cols, node in _readers(plan)
            if table == "lineitem"]
    assert line == [["l_orderkey", "l_quantity"]] * 2
    txt = plan.explain()
    assert txt.count("table:lineitem, cols:2/16") == 2
    assert "table:orders, cols:4/9" in txt
    assert "table:customer, cols:2/8" in txt


@pytest.mark.parametrize("sql,index,cols", [
    ("SELECT b FROM t WHERE b >= 20", "ib", ["b"]),
    ("SELECT a, b FROM t WHERE b = 20", "ib", ["a", "b"]),
    ("SELECT d, b FROM t WHERE b = 20 AND d > 'x'", "ibd", ["b", "d"]),
    ("SELECT COUNT(*) FROM t WHERE b = 20", None, ["b"]),
], ids=["one_column", "with_handle", "two_columns", "agg_stays_on_table"])
def test_covering_index_is_chosen_and_answers_as_the_table_scan(
        tk, monkeypatch, sql, index, cols):
    """A six-column table's reader that asks for indexed columns alone
    reaches `_choose_access_path`'s covering test; at full width it
    could not."""
    got, want, readers = _both(tk, monkeypatch, sql)
    assert got == want and got
    (_table, asked, node), = readers
    assert sorted(asked) == cols
    if index is None:
        assert isinstance(node, ph.PhysTableReader)
    else:
        assert isinstance(node, ph.PhysIndexReader)
        assert node.cop.index.name.lower() == index
        ignore = sql.replace("FROM t", "FROM t IGNORE INDEX (ib, ibd)")
        scan = tk.query(ignore).rows
        assert isinstance(_readers(tk.plan(ignore))[0][2],
                          ph.PhysTableReader)
        assert sorted(scan, key=repr) == got


@pytest.mark.parametrize("sql,kind,cols", [
    ("SELECT c FROM t USE INDEX (ibd) WHERE b = 20",
     ph.PhysIndexLookUp, ["b", "c"]),
    ("SELECT c FROM t FORCE INDEX (ibd) WHERE b >= 20",
     ph.PhysIndexLookUp, ["b", "c"]),
    ("SELECT c, a FROM t IGNORE INDEX (ib) WHERE b IN (10, 30)",
     ph.PhysIndexLookUp, ["a", "b", "c"]),
    ("SELECT b FROM t USE INDEX (ibd) WHERE b = 20",
     ph.PhysIndexReader, ["b"]),
    ("SELECT a FROM t USE INDEX (ibd) WHERE b > 10",
     ph.PhysIndexReader, ["a", "b"]),
], ids=["lookup_eq", "lookup_range", "lookup_in", "covers", "covers_handle"])
def test_composite_index_serves_a_reader_that_names_its_prefix(
        tk, monkeypatch, sql, kind, cols):
    """`KEY ibd (b, d)` stays an access path when the statement names
    `b` alone: the pruned schema holds the index's leading column, and a
    column nobody names can carry no range."""
    got, want, readers = _both(tk, monkeypatch, sql)
    assert got == want and got
    (_table, asked, node), = readers
    assert sorted(asked) == cols
    assert isinstance(node, kind)
    cop = node.index_cop if kind is ph.PhysIndexLookUp else node.cop
    assert cop.index.name.lower() == "ibd"
    # the whole-row planner took the same index (covering apart: only a
    # narrow reader can be covered), and the table scan answers alike
    with monkeypatch.context() as m:
        m.setattr(planner._ColumnReads, "of",
                  lambda self, ref, info: info.public_columns())
        (_t, _c, whole), = _readers(tk.plan(sql))
    assert isinstance(whole, ph.PhysIndexLookUp)
    assert whole.index_cop.index.name.lower() == "ibd"
    assert whole.index_cop.ranges == cop.ranges
    ignore = sql.split(" FROM t ")[0] + " FROM t IGNORE INDEX (ib, ibd) " \
        "WHERE" + sql.split(" WHERE", 1)[1]
    assert isinstance(_readers(tk.plan(ignore))[0][2], ph.PhysTableReader)
    assert sorted(tk.query(ignore).rows, key=repr) == got


DML_CASES = [
    ("update", "UPDATE t SET c = c + 1 WHERE b = 20",
     "SELECT * FROM t ORDER BY a"),
    ("update_subquery", "UPDATE t SET d = 'in' WHERE b IN "
     "(SELECT b FROM u WHERE h > 6)", "SELECT * FROM t ORDER BY a"),
    ("delete", "DELETE FROM t WHERE d = 'x'", "SELECT * FROM t ORDER BY a"),
    ("multi_delete", "DELETE t, u FROM t JOIN u ON t.a = u.a WHERE u.h > 7",
     "SELECT t.a, t.d, u.a, u.g FROM t LEFT JOIN u ON t.a = u.a "
     "UNION ALL SELECT NULL, NULL, a, g FROM u ORDER BY 1, 3"),
    ("multi_update", "UPDATE t, u SET t.d = u.g, u.h = t.b "
     "WHERE t.a = u.a", "SELECT t.a, t.d, t.f, u.h, u.g FROM t, u "
     "WHERE t.a = u.a ORDER BY 1"),
    ("insert_select", "INSERT INTO u (a, b, g) SELECT a + 100, b, d FROM t "
     "WHERE c > 2", "SELECT * FROM u ORDER BY a"),
    ("insert_select_star", "INSERT INTO t SELECT a + 100, b, c, d, e, f "
     "FROM t WHERE a < 3", "SELECT * FROM t ORDER BY a"),
]


@pytest.mark.parametrize("dml,check", [c[1:] for c in DML_CASES],
                         ids=[c[0] for c in DML_CASES])
def test_dml_readers_keep_row_and_handle(tk, monkeypatch, dml, check):
    """UPDATE / DELETE and the multi-table forms rewrite whole rows: their
    target readers keep every column and the handle, and the row left
    behind is the one whole-row planning leaves. An INSERT ... SELECT's
    source is a query body and is pruned."""
    tk.execute("USE test")
    plan = tk.plan(dml)
    for _depth, node in plan.explain_nodes():
        if isinstance(node, ph.PhysTableReader) and \
                node.cop.handle_col is not None:
            assert len(node.cop.cols) == len(node.cop.table.public_columns())
            assert node.schema.cols[node.cop.handle_col].name == "_handle"
    if dml.startswith("INSERT INTO u"):
        assert _cols_by_table(_readers(plan)) == {"t": [["a", "b", "c", "d"]]}

    def run_and_undo():
        tk.execute("BEGIN")
        try:
            tk.execute(dml)
            return tk.query(check).rows
        finally:
            tk.execute("ROLLBACK")

    got = run_and_undo()
    with monkeypatch.context() as m:
        m.setattr(planner._ColumnReads, "of",
                  lambda self, ref, info: info.public_columns())
        want = run_and_undo()
    assert got == want
    assert got != tk.query(check).rows      # the statement did something
