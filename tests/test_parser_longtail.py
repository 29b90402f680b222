"""Grammar long tail mined from the reference's parser corpus
(ref: parser/parser_test.go, 2.1k LoC of table cases; VERDICT r4 #6).
Each case here parses AND the statement classes carry the right data."""

import pytest

from tidb_tpu.parser import ast
from tidb_tpu.parser.parser import ParseError, parse


def one(sql):
    stmts = parse(sql)
    assert len(stmts) == 1
    return stmts[0]


PARSES = [
    # column/type long tail
    "CREATE TABLE foo (name CHAR(50) BINARY)",
    "CREATE TABLE foo (name CHAR(50) CHARACTER SET utf8)",
    "CREATE TABLE foo (name CHAR(50) BINARY CHARACTER SET utf8 "
    "COLLATE utf8_bin)",
    "CREATE TABLE t (c TEXT) default CHARACTER SET utf8, "
    "default COLLATE utf8_general_ci",
    "CREATE TABLE t (a int1, b int2, c int3, d int4, e int8)",
    "CREATE TABLE t (c1 national char(2), c2 national varchar(2))",
    "CREATE TABLE t (ts timestamp NOT NULL DEFAULT CURRENT_TIMESTAMP "
    "ON UPDATE CURRENT_TIMESTAMP)",
    "CREATE TABLE c (sd integer CHECK (sd > 0), nm varchar(30))",
    "CREATE TABLE t (c1 bool, check (c1 in (0, 1)))",
    "CREATE TABLE t (id int, PRIMARY KEY pk_id (id))",
    "CREATE TABLE t (v varbinary(16), m mediumtext, l longblob)",
    # table options / partitioning
    "CREATE TABLE p (id bigint) ENGINE=InnoDB AUTO_INCREMENT=6 "
    "DEFAULT CHARSET=utf8 ROW_FORMAT=COMPRESSED KEY_BLOCK_SIZE=8",
    "CREATE TABLE t (c int) PARTITION BY HASH (c) PARTITIONS 32",
    # indexes
    "CREATE INDEX idx ON t (a) USING HASH COMMENT 'foo'",
    "CREATE INDEX idx USING BTREE ON t (a)",
    "CREATE TABLE t (a int, INDEX ia (a) COMMENT 'x', "
    "FULLTEXT KEY ft (a))",
    # ALTER long tail
    "ALTER TABLE t ADD COLUMN (a SMALLINT UNSIGNED, b varchar(255))",
    "ALTER TABLE t DISABLE KEYS",
    "ALTER TABLE t ENABLE KEYS",
    "ALTER TABLE t CHANGE COLUMN a b varchar(255) FIRST",
    "ALTER TABLE t ALTER COLUMN a SET DEFAULT 1",
    "ALTER TABLE t ALTER a DROP DEFAULT",
    "ALTER TABLE t ADD COLUMN a SMALLINT UNSIGNED, lock=none",
    "ALTER TABLE t ADD UNIQUE (a) COMMENT 'a'",
    "ALTER TABLE t ENGINE = innodb",
    "ALTER TABLE t ADD FULLTEXT INDEX ft (nm ASC)",
    # SELECT long tail
    "SELECT DISTINCTROW * FROM t",
    "SELECT a.b.* FROM t",
    "SELECT * from t lock in share mode",
    "SELECT SUBSTRING('Quadratically' FROM 5)",
    "SELECT SUBSTRING('Quadratically' FROM 5 FOR 3)",
    "SELECT CAST(data AS CHAR CHARACTER SET utf8) FROM t",
    "SELECT CAST(data AS JSON) FROM t",
    "SELECT CAST(1 AS SIGNED INT)",
    "SELECT X'0a', 0x0b, b'1010'",
    "SELECT N'string'",
    "SELECT 1 AS 'a'",
    "select * from t1 straight_join t2 on t1.id = t2.id",
    "(select c1 from t1) union distinctrow select c2 from t2",
    # SET long tail
    "SET LOCAL autocommit = 1",
    "SET @@local.autocommit = 1",
    "SET PASSWORD FOR 'root'@'localhost' = 'password'",
    "SET SESSION TRANSACTION ISOLATION LEVEL REPEATABLE READ",
    "SET GLOBAL TRANSACTION ISOLATION LEVEL READ COMMITTED",
    "SET SESSION TRANSACTION READ ONLY",
    # SHOW / FLUSH / DROP / ADMIN / ANALYZE
    "SHOW CHARACTER SET",
    "SHOW CHARSET",
    "SHOW FULL COLUMNS IN t",
    "SHOW STATS_META",
    "SHOW STATS_BUCKETS WHERE table_name = 't'",
    "FLUSH NO_WRITE_TO_BINLOG TABLES tbl1 WITH READ LOCK",
    "FLUSH TABLES tbl1, tbl2",
    "DROP TABLES xxx, yyy",
    "DROP VIEW IF EXISTS xxx",
    "DROP STATS t",
    "ADMIN CANCEL DDL JOBS 1, 2",
    "ANALYZE TABLE t1 INDEX a, b",
    # misc
    "INSERT INTO foo () VALUES ()",
    "CREATE TABLE a LIKE b",
    "CREATE TABLE IF NOT EXISTS a LIKE b",
    "ALTER TABLE db.t RENAME db.t1",
    "GRANT ALL ON db1.* TO 'jeffrey'@'localhost' WITH GRANT OPTION",
]


@pytest.mark.parametrize("sql", PARSES)
def test_parses(sql):
    parse(sql)


class TestSemantics:
    def test_hex_literal_value(self):
        s = one("SELECT X'0a' + 0")
        assert isinstance(s, ast.SelectStmt)

    def test_create_like_ast(self):
        s = one("CREATE TABLE a LIKE b")
        assert s.like_table.name == "b"

    def test_alter_set_default(self):
        s = one("ALTER TABLE t ALTER COLUMN a SET DEFAULT 1")
        assert s.specs[0].tp == "set_default"
        assert s.specs[0].name == "a"

    def test_substring_from_desugars(self):
        s = one("SELECT SUBSTRING('abcdef' FROM 2 FOR 3)")
        f = s.fields[0].expr
        assert isinstance(f, ast.FuncCall) and len(f.args) == 3

    def test_admin_cancel_ids(self):
        s = one("ADMIN CANCEL DDL JOBS 3, 4")
        assert s.tp == "cancel_ddl_jobs" and s.job_ids == [3, 4]

    def test_grant_option_adds_grant_priv(self):
        s = one("GRANT SELECT ON d.* TO 'u'@'%' WITH GRANT OPTION")
        assert "GRANT" in s.privs

    def test_multi_schema_alter_still_rejected(self):
        with pytest.raises(ParseError):
            parse("ALTER TABLE t ADD COLUMN a INT ADD COLUMN b INT")


class TestEndToEnd:
    """The new syntax runs through the session, not just the parser."""

    @pytest.fixture
    def sess(self):
        from tidb_tpu.bootstrap import bootstrap
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        st = new_mock_storage()
        bootstrap(st)           # SET PASSWORD touches mysql.user
        s = Session(st)
        s.execute("CREATE DATABASE lt; USE lt")
        yield s
        s.close()

    def test_create_like_clones_schema(self, sess):
        sess.execute("CREATE TABLE src (id BIGINT PRIMARY KEY, "
                     "v VARCHAR(10) COLLATE utf8mb4_general_ci)")
        sess.execute("CREATE INDEX iv ON src (v)")
        sess.execute("CREATE TABLE dst LIKE src")
        sess.execute("INSERT INTO dst VALUES (1, 'X')")
        assert sess.query("SELECT COUNT(*) FROM dst WHERE v = 'x'"
                          ).rows == [(1,)]
        # independent tables
        assert sess.query("SELECT COUNT(*) FROM src").rows == [(0,)]

    def test_set_password_and_transaction(self, sess):
        sess.execute("CREATE USER 'u1'@'%'")
        sess.execute("SET PASSWORD FOR 'u1'@'%' = 'secret'")
        from tidb_tpu.privilege import encode_password
        assert sess.query(
            "SELECT authentication_string FROM mysql.user "
            "WHERE user = 'u1'").rows == [(encode_password("secret"),)]
        sess.execute("SET SESSION TRANSACTION ISOLATION LEVEL "
                     "READ COMMITTED")

    def test_alter_set_default_applies(self, sess):
        sess.execute("CREATE TABLE d (id BIGINT PRIMARY KEY, v BIGINT)")
        sess.execute("ALTER TABLE d ALTER COLUMN v SET DEFAULT 42")
        sess.execute("INSERT INTO d (id) VALUES (1)")
        assert sess.query("SELECT v FROM d").rows == [(42,)]
        sess.execute("ALTER TABLE d ALTER COLUMN v DROP DEFAULT")

    def test_show_stats_after_analyze(self, sess):
        sess.execute("CREATE TABLE st (id BIGINT PRIMARY KEY, v BIGINT)")
        sess.execute("INSERT INTO st VALUES " + ",".join(
            f"({i},{i % 7})" for i in range(100)))
        sess.execute("ANALYZE TABLE st")
        rows = sess.query("SHOW STATS_META WHERE table_name = 'st'").rows
        assert len(rows) == 1 and rows[0][4] == 100
        assert sess.query("SHOW STATS_HISTOGRAMS "
                          "WHERE table_name = 'st'").rows
        assert sess.query("SHOW STATS_BUCKETS "
                          "WHERE table_name = 'st'").rows

    def test_drop_stats(self, sess):
        sess.execute("CREATE TABLE ds (id BIGINT PRIMARY KEY)")
        sess.execute("INSERT INTO ds VALUES (1)")
        sess.execute("ANALYZE TABLE ds")
        sess.execute("DROP STATS ds")
        assert sess.query("SHOW STATS_META WHERE table_name = 'ds'"
                          ).rows == []

    def test_admin_cancel_missing_job(self, sess):
        rows = sess.query("ADMIN CANCEL DDL JOBS 99999").rows
        assert rows == [(99999, "not found")]

    def test_flush_tables_and_drop_view(self, sess):
        sess.execute("FLUSH TABLES")
        sess.execute("DROP VIEW IF EXISTS nothing")
        from tidb_tpu.session import SQLError
        with pytest.raises(SQLError):
            sess.execute("DROP VIEW nothing")


class TestMultiTableDelete:
    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE md; USE md")
        s.execute("CREATE TABLE t1 (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute("CREATE TABLE t2 (id BIGINT PRIMARY KEY, v BIGINT)")
        s.execute("CREATE TABLE t3 (id BIGINT PRIMARY KEY)")
        s.execute("INSERT INTO t1 VALUES (1, 10), (2, 20), (3, 30)")
        s.execute("INSERT INTO t2 VALUES (1, 1), (3, 3), (4, 4)")
        s.execute("INSERT INTO t3 VALUES (1), (3)")
        yield s
        s.close()

    def test_delete_from_two_targets(self, sess):
        sess.execute("DELETE t1, t2 FROM t1 INNER JOIN t2 "
                     "ON t1.id = t2.id WHERE t1.id > 0")
        # matched ids 1 and 3 deleted from both; unmatched stay
        assert sess.query("SELECT id FROM t1 ORDER BY id").rows == [(2,)]
        assert sess.query("SELECT id FROM t2 ORDER BY id").rows == [(4,)]

    def test_using_form_with_extra_table(self, sess):
        sess.execute("DELETE FROM t1 USING t1 INNER JOIN t3 "
                     "ON t1.id = t3.id")
        assert sess.query("SELECT id FROM t1 ORDER BY id").rows == [(2,)]
        # t3 was only a filter source, untouched
        assert sess.query("SELECT COUNT(*) FROM t3").rows == [(2,)]

    def test_indexes_maintained(self, sess):
        sess.execute("CREATE INDEX iv ON t1 (v)")
        sess.execute("DELETE t1 FROM t1 INNER JOIN t2 ON t1.id = t2.id")
        assert sess.query("SELECT id FROM t1 WHERE v = 10").rows == []
        assert sess.query("SELECT id FROM t1 WHERE v = 20").rows == [(2,)]

    def test_rollback(self, sess):
        sess.execute("BEGIN")
        sess.execute("DELETE t1, t2 FROM t1 INNER JOIN t2 "
                     "ON t1.id = t2.id")
        sess.execute("ROLLBACK")
        assert sess.query("SELECT COUNT(*) FROM t1").rows == [(3,)]
        assert sess.query("SELECT COUNT(*) FROM t2").rows == [(3,)]


class TestReviewRegressions:
    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE rr; USE rr")
        yield s
        s.close()

    def test_change_column_first_reorders(self, sess):
        sess.execute("CREATE TABLE c (a BIGINT PRIMARY KEY, b BIGINT)")
        sess.execute("INSERT INTO c VALUES (1, 2)")
        sess.execute("ALTER TABLE c CHANGE COLUMN b b2 BIGINT FIRST")
        rows = sess.query("SELECT * FROM c").rows
        assert rows == [(2, 1)]          # b2 now leads
        cols = [r[0] for r in sess.query("SHOW COLUMNS FROM c").rows]
        assert cols[0] == "b2"

    def test_multi_delete_needs_privs(self):
        from tidb_tpu.bootstrap import bootstrap
        from tidb_tpu.session import Session, SQLError
        from tidb_tpu.store.storage import new_mock_storage
        st = new_mock_storage()
        bootstrap(st)
        r = Session(st, user="root", host="%")
        r.execute("CREATE DATABASE pd2; USE pd2")
        r.execute("CREATE TABLE t1 (id BIGINT PRIMARY KEY)")
        r.execute("CREATE TABLE t2 (id BIGINT PRIMARY KEY)")
        r.execute("INSERT INTO t1 VALUES (1)")
        r.execute("INSERT INTO t2 VALUES (1)")
        r.execute("CREATE USER w")
        r.execute("GRANT DELETE ON pd2.t1 TO w")
        s = Session(st, user="w", host="localhost")
        s.execute("USE pd2")
        # DELETE priv on t1 but no SELECT on t2: the join read is denied
        with pytest.raises(SQLError, match="SELECT"):
            s.execute("DELETE t1 FROM t1 INNER JOIN t2 "
                      "ON t1.id = t2.id")
        r.execute("GRANT SELECT ON pd2.t1 TO w")
        r.execute("GRANT SELECT ON pd2.t2 TO w")
        s.execute("DELETE t1 FROM t1 INNER JOIN t2 ON t1.id = t2.id")
        s.close()
        assert r.query("SELECT COUNT(*) FROM t1").rows == [(0,)]
        r.close()

    def test_set_own_password_matches_host_pattern(self):
        from tidb_tpu.bootstrap import bootstrap
        from tidb_tpu.privilege import encode_password
        from tidb_tpu.session import Session, SQLError
        from tidb_tpu.store.storage import new_mock_storage
        st = new_mock_storage()
        bootstrap(st)
        r = Session(st, user="root", host="%")
        r.execute("CREATE USER 'u'@'localhost'")
        s = Session(st, user="u", host="localhost")
        s.execute("SET PASSWORD = 'mine'")      # no FOR: own account
        assert r.query("SELECT authentication_string FROM mysql.user "
                       "WHERE user = 'u'").rows == \
            [(encode_password("mine"),)]
        # FOR any account needs CREATE USER
        with pytest.raises(SQLError):
            s.execute("SET PASSWORD FOR 'root'@'%' = 'x'")
        s.close()
        r.close()


class TestThirdReviewRegressions:
    def test_multi_delete_where_subquery_needs_select(self):
        from tidb_tpu.bootstrap import bootstrap
        from tidb_tpu.session import Session, SQLError
        from tidb_tpu.store.storage import new_mock_storage
        st = new_mock_storage()
        bootstrap(st)
        r = Session(st, user="root", host="%")
        r.execute("CREATE DATABASE p3; USE p3")
        r.execute("CREATE TABLE t1 (id BIGINT PRIMARY KEY)")
        r.execute("CREATE TABLE t2 (id BIGINT PRIMARY KEY)")
        r.execute("CREATE DATABASE other")
        r.execute("CREATE TABLE other.secret (id BIGINT PRIMARY KEY)")
        r.execute("CREATE USER w2")
        for t in ("t1", "t2"):
            r.execute(f"GRANT DELETE ON p3.{t} TO w2")
            r.execute(f"GRANT SELECT ON p3.{t} TO w2")
        s = Session(st, user="w2", host="localhost")
        s.execute("USE p3")
        with pytest.raises(SQLError, match="SELECT"):
            s.execute("DELETE t1 FROM t1 INNER JOIN t2 ON t1.id=t2.id "
                      "WHERE t1.id IN (SELECT id FROM other.secret)")
        s.close(); r.close()

    def test_set_password_prefers_specific_host(self):
        from tidb_tpu.bootstrap import bootstrap
        from tidb_tpu.privilege import encode_password
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        st = new_mock_storage()
        bootstrap(st)
        r = Session(st, user="root", host="%")
        r.execute("CREATE USER 'u'@'%' IDENTIFIED BY 'wild'")
        r.execute("CREATE USER 'u'@'localhost' IDENTIFIED BY 'loc'")
        s = Session(st, user="u", host="localhost")
        s.execute("SET PASSWORD = 'newpw'")
        rows = dict(r.query(
            "SELECT host, authentication_string FROM mysql.user "
            "WHERE user = 'u'").rows)
        assert rows["localhost"] == encode_password("newpw")
        assert rows["%"] == encode_password("wild")   # untouched
        s.close(); r.close()

    def test_change_after_self_rejected_at_submit(self):
        from tidb_tpu.session import Session, SQLError
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE a3; USE a3")
        s.execute("CREATE TABLE c (a BIGINT PRIMARY KEY, b BIGINT)")
        with pytest.raises(SQLError, match="Unknown column"):
            s.execute("ALTER TABLE c CHANGE COLUMN b b2 BIGINT AFTER b")
        with pytest.raises(SQLError, match="Unknown column"):
            s.execute("ALTER TABLE c CHANGE COLUMN b b2 BIGINT "
                      "AFTER b2")
        s.close()


class TestMinedExprCases:
    """Harvested from the reference's executor test corpus (table-free
    MustQuery cases run against our session)."""

    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE mx; USE mx")
        yield s
        s.close()

    def test_last_insert_id(self, sess):
        sess.execute("CREATE TABLE a (id BIGINT PRIMARY KEY "
                     "AUTO_INCREMENT, v BIGINT)")
        sess.execute("INSERT INTO a (v) VALUES (7), (8)")
        first = sess.query("SELECT LAST_INSERT_ID()").rows[0][0]
        assert first >= 1
        sess.execute("INSERT INTO a (v) VALUES (9)")
        second = sess.query("SELECT LAST_INSERT_ID()").rows[0][0]
        assert second > first    # first id of the LATEST insert

    def test_show_warnings_and_empty_catalogs(self, sess):
        assert sess.query("SHOW WARNINGS").rows == []
        assert sess.query("SHOW ERRORS").rows == []
        assert sess.query("SHOW PLUGINS").rows == []
        assert sess.query("SHOW PROFILES").rows == []
        assert sess.query("SHOW TRIGGERS").rows == []
        assert sess.query("SHOW EVENTS WHERE Db = 'x'").rows == []
        assert sess.query("SHOW PROCEDURE STATUS").rows == []
        assert sess.query("SHOW MASTER STATUS").rows == []

    def test_unhex_binary_round_trip(self, sess):
        assert sess.query("SELECT HEX(UNHEX('FF'))").rows == [("FF",)]
        assert sess.query(
            "SELECT INET6_NTOA(UNHEX("
            "'FDFE0000000000005A55CAFFFEFA9089'))").rows == \
            [("fdfe::5a55:caff:fefa:9089",)]

    def test_sleep_bad_arg_clean_error(self, sess):
        from tidb_tpu.session import SQLError
        with pytest.raises(SQLError, match="sleep"):
            sess.query("SELECT SLEEP('a')")

    def test_wide_literal_multiply_exact(self, sess):
        from decimal import Decimal, localcontext
        got = sess.query(
            "select 123344532434234234267890.0 * "
            "1234567118923479823749823749.230").rows[0][0]
        with localcontext() as ctx:
            ctx.prec = 70
            want = (Decimal("123344532434234234267890.0") *
                    Decimal("1234567118923479823749823749.230"))
            assert Decimal(got) == want


class TestFifthReviewRegressions:
    """Fixes from the fifth review pass."""

    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE rv5; USE rv5")
        yield s
        s.close()

    def test_last_insert_id_ignores_hidden_rowid(self, sess):
        sess.execute("CREATE TABLE noauto (a INT, b INT)")
        sess.execute("INSERT INTO noauto VALUES (1, 2)")
        # hidden _tidb_rowid allocation must NOT leak into
        # LAST_INSERT_ID (MySQL: 0 when no AUTO_INCREMENT was used)
        assert sess.query("SELECT LAST_INSERT_ID()").rows == [(0,)]

    def test_unhex_uniform_bytes_sort_and_compare(self, sess):
        sess.execute("CREATE TABLE hx (h VARCHAR(32))")
        sess.execute("INSERT INTO hx VALUES ('41'), ('FF'), ('42')")
        rows = sess.query("SELECT HEX(UNHEX(h)) FROM hx "
                          "ORDER BY UNHEX(h)").rows
        assert [r[0] for r in rows] == ["41", "42", "FF"]
        # bytes vs str literal comparison must not raise
        rows = sess.query(
            "SELECT h FROM hx WHERE UNHEX(h) = 'A'").rows
        assert rows == [("41",)]
        assert sess.query(
            "SELECT LENGTH(UNHEX('FF41'))").rows == [(2,)]

    def test_show_warnings_populated_and_cleared(self, sess):
        sess.execute("DROP TABLE IF EXISTS ghost")
        rows = sess.query("SHOW WARNINGS").rows
        assert rows == [("Note", 1051, "Unknown table 'rv5.ghost'")]
        # SHOW WARNINGS itself does not clear the area
        assert sess.query("SHOW WARNINGS").rows == rows
        # errors-only view filters out notes
        assert sess.query("SHOW ERRORS").rows == []
        # any other statement resets the diagnostics area
        sess.query("SELECT 1")
        assert sess.query("SHOW WARNINGS").rows == []


class TestSessionLongtail:
    """SHOW ... WHERE, no-FROM aggregates, user variables, PREPARE FROM."""

    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE lt; USE lt")
        yield s
        s.close()

    def test_show_variables_where(self, sess):
        rows = sess.query("show global variables where "
                          "variable_name = 'autocommit'").rows
        assert rows == [("autocommit", "1")]
        rows = sess.query("show variables where "
                          "Variable_name = 'sql_mode'").rows
        assert rows == [("sql_mode", "STRICT_TRANS_TABLES")]

    def test_no_from_aggregates(self, sess):
        assert sess.query("select sum(1.2e2) * 0.1").rows == [(12.0,)]
        assert sess.query("select count(*)").rows == [(1,)]
        assert sess.query("select max(3) + min(2)").rows == [(5,)]

    def test_user_var_assignment(self, sess):
        assert sess.query("select @tmp1 := 11, @tmp2").rows == \
            [(11, None)]
        assert sess.query("select @tmp1").rows == [(11,)]
        # left-to-right: later items see earlier assignments
        assert sess.query(
            "select @x := 1 + 2, @y := concat('a','b'), @x + 1"
        ).rows == [(3, "ab", 4)]

    def test_prepare_from_user_variable(self, sess):
        sess.execute("SET @q = 'select ? + 1'")
        sess.execute("PREPARE st FROM @q")
        sess.execute("SET @v = 41")
        assert sess.query("execute st using @v").rows == [(42,)]
        sess.execute("DEALLOCATE PREPARE st")
        from tidb_tpu.session import SQLError
        with pytest.raises(SQLError):
            sess.query("execute st using @v")

    def test_sixth_review_regressions(self, sess):
        from tidb_tpu.session import SQLError
        # UNHEX IN-list: binary column lifts for the membership test
        sess.execute("CREATE TABLE hx6 (h VARCHAR(32))")
        sess.execute("INSERT INTO hx6 VALUES ('41'), ('FF'), ('42')")
        rows = sess.query("SELECT h FROM hx6 WHERE UNHEX(h) IN "
                          "('A','B') ORDER BY h").rows
        assert [r[0] for r in rows] == ["41", "42"]
        # no-FROM aggregate honors LIMIT/OFFSET
        assert sess.query("SELECT COUNT(*) LIMIT 0").rows == []
        assert sess.query("SELECT COUNT(*) LIMIT 1").rows == [(1,)]
        # SHOW ... WHERE compares case-insensitively
        assert sess.query("show variables where variable_name = "
                          "'AUTOCOMMIT'").rows == [("autocommit", "1")]
        # @v := <bad expr> keeps the SQLError contract
        with pytest.raises(SQLError):
            sess.query("select @e := sleep('x')")


class TestMinedFlowFixes:
    """Fixes surfaced by replaying reference executor-test flows."""

    @pytest.fixture
    def sess(self):
        from tidb_tpu.session import Session
        from tidb_tpu.store.storage import new_mock_storage
        s = Session(new_mock_storage())
        s.execute("CREATE DATABASE mf; USE mf")
        yield s
        s.close()

    def test_having_without_group_by(self, sess):
        sess.execute("CREATE TABLE t (c1 INT, c3 INT)")
        sess.execute("INSERT INTO t VALUES (1,3),(2,1),(3,2)")
        assert sess.query(
            "select c1 as c2, c3 from t having c2 = 2").rows == [(2, 1)]
        assert sess.query(
            "select t.c1 from t having c1 = 1").rows == [(1,)]

    def test_positional_order_by_star(self, sess):
        sess.execute("CREATE TABLE t (a INT, b INT)")
        sess.execute("INSERT INTO t VALUES (1,2),(2,1)")
        assert sess.query("select * from t order by 2").rows == \
            [(2, 1), (1, 2)]

    def test_insert_empty_values(self, sess):
        sess.execute("CREATE TABLE t (id BIGINT PRIMARY KEY "
                     "AUTO_INCREMENT, v INT DEFAULT 7)")
        sess.execute("INSERT INTO t VALUES ()")
        sess.execute("INSERT INTO t VALUES (), ()")
        assert sess.query("select * from t order by id").rows == \
            [(1, 7), (2, 7), (3, 7)]

    def test_auto_increment_sequential_across_statements(self, sess):
        sess.execute("CREATE TABLE t (id BIGINT PRIMARY KEY "
                     "AUTO_INCREMENT, v INT)")
        for v in (11, 22, 33):
            sess.execute(f"INSERT INTO t (v) VALUES ({v})")
        assert sess.query("select id from t order by id").rows == \
            [(1,), (2,), (3,)]
        # explicit id inside the cached batch: skip past it, not +4000
        sess.execute("INSERT INTO t VALUES (100, 44)")
        sess.execute("INSERT INTO t (v) VALUES (55)")
        assert sess.query("select max(id) from t").rows == [(101,)]

    def test_index_hints_and_prefix_index(self, sess):
        sess.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT, "
                     "KEY idx(v))")
        sess.execute("INSERT INTO t VALUES (1, 10), (2, 20)")
        assert sess.query("select * from t ignore index(idx) "
                          "where v = 10").rows == [(1, 10)]
        assert sess.query("select * from t force index(idx) "
                          "where v = 20").rows == [(2, 20)]
        sess.execute("create index idx_p on t (v(3))")

    def test_set_do_user_vars_and_current_ts(self, sess):
        sess.execute("SET @tmp = 1; SET @tmp := @tmp + 1")
        assert sess.query("select @tmp").rows == [(2,)]
        sess.execute("do 1, @a := 5")
        assert sess.query("select @a").rows == [(5,)]
        assert sess.query("select @@tidb_current_ts").rows == [(0,)]

    def test_enum_numeric_context(self, sess):
        sess.execute("CREATE TABLE t (c ENUM('a','b','c'))")
        sess.execute("INSERT INTO t VALUES ('b'), ('a')")
        assert sess.query("select c + 1 from t where c = 2").rows == \
            [(3,)]
        assert sess.query("select c from t where c = 'b'").rows == \
            [("b",)]

    def test_sum_string_prefix_coercion(self, sess):
        sess.execute("CREATE TABLE t (id INT, b VARCHAR(10))")
        sess.execute("INSERT INTO t VALUES (1, '1ff'), (1, '2')")
        assert sess.query("select id, sum(b) from t group by id"
                          ).rows == [(1, 3.0)]

    def test_information_schema_charsets(self, sess):
        rows = sess.query(
            "SELECT CHARACTER_SET_NAME FROM "
            "INFORMATION_SCHEMA.CHARACTER_SETS WHERE MAXLEN = 4").rows
        assert rows == [("utf8mb4",)]
        assert len(sess.query(
            "SELECT * FROM INFORMATION_SCHEMA.COLLATIONS").rows) >= 4

    def test_seventh_review_regressions(self, sess):
        from tidb_tpu.session import SQLError
        # SET applies left-to-right within one statement
        sess.execute("SET @a7 = 1, @b7 = @a7 + 1")
        assert sess.query("select @a7, @b7").rows == [(1, 2)]
        # HAVING: a real column shadows the select alias
        sess.execute("CREATE TABLE sh (c1 INT, c2 INT)")
        sess.execute("INSERT INTO sh VALUES (5, 9)")
        assert sess.query("SELECT c1 AS c2, c2 AS x FROM sh "
                          "HAVING c2 = 5").rows == []
        assert sess.query("SELECT c1 AS z FROM sh HAVING z = 5"
                          ).rows == [(5,)]
        # () shorthand is illegal with an explicit column list
        sess.execute("CREATE TABLE a7 (id BIGINT PRIMARY KEY "
                     "AUTO_INCREMENT, v INT)")
        with pytest.raises(SQLError, match="Column count"):
            sess.execute("INSERT INTO a7 (v) VALUES ()")
