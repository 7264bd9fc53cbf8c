"""Query lifecycle: parse -> analyze/plan -> optimize -> execute.

Reference: ``execution/SqlQueryExecution.java:393`` (start -> analyze ->
planQuery -> planDistribution -> schedule); collapsed here to the local path.
EXPLAIN mirrors sql/planner/planprinter/PlanPrinter.
"""
from __future__ import annotations

from trino_tpu.exec.executor import Executor, QueryResult
from trino_tpu.sql.parser import ast
from trino_tpu.sql.parser.parser import parse_statement
from trino_tpu.sql.planner.optimizer import optimize, stamp_join_estimates
from trino_tpu.sql.planner.plan import format_plan
from trino_tpu.sql.planner.planner import Planner


def plan_sql(session, sql: str):
    from trino_tpu.obs import trace as tracing

    with tracing.span("parse"):
        stmt = parse_statement(sql)
    if isinstance(stmt, ast.Explain):
        raise ValueError("use explain_query")
    if not isinstance(stmt, ast.Query):
        return stmt  # SHOW et al, handled by run_query
    udfs = getattr(session, "udfs", None)
    if udfs:
        from trino_tpu.sql.routines import expand_udfs

        stmt = expand_udfs(stmt, udfs)
    with tracing.span("analyze/plan"):
        root = Planner(session).plan(stmt)
    with tracing.span("optimize") as sp:
        return optimize(root, session, span=sp)


def run_query(session, sql: str) -> QueryResult:
    return _dispatch_statement(session, parse_statement(sql), sql=sql)


def dispatch_statement(session, stmt) -> QueryResult:
    """Run an already-parsed statement (the coordinator's EXECUTE path
    dispatches the stored prepared AST without re-parsing)."""
    return _dispatch_statement(session, stmt)


def bind_parameters(stmt, params):
    """Substitute ``?`` placeholders with the EXECUTE ... USING expressions
    (reference: planner/ParameterRewriter): a generic rewrite over the
    frozen AST. Arity must match exactly — too many bindings is as much a
    caller bug as too few."""
    from trino_tpu.server.prepared import count_parameters

    need = count_parameters(stmt)
    if len(params) != need:
        raise ValueError(
            f"prepared statement expects {need} parameters, "
            f"got {len(params)}")
    return _bind_parameters(stmt, params)


def _bind_parameters(stmt, params):
    import dataclasses as _dc

    def rewrite(node):
        if isinstance(node, ast.Parameter):
            if node.index >= len(params):
                raise ValueError(
                    f"prepared statement needs {node.index + 1} parameters, "
                    f"got {len(params)}")
            return params[node.index]
        if isinstance(node, tuple):
            return tuple(rewrite(x) for x in node)
        if _dc.is_dataclass(node) and not isinstance(node, type):
            changes = {}
            for f in _dc.fields(node):
                v = getattr(node, f.name)
                nv = rewrite(v)
                if nv is not v:
                    changes[f.name] = nv
            return _dc.replace(node, **changes) if changes else node
        return node

    return rewrite(stmt)


def _dispatch_statement(session, stmt, sql=None) -> QueryResult:
    if isinstance(stmt, ast.Explain):
        if stmt.analyze:
            text = explain_analyze(session, stmt.statement,
                                   verbose=stmt.verbose)
        else:
            text = explain_query(session, None, stmt.mode, stmt=stmt.statement)
        return QueryResult(["Query Plan"], [], [(line,) for line in text.split("\n")])
    if isinstance(stmt, ast.CreateTable):
        return _create_table(session, stmt)
    if isinstance(stmt, ast.CreateTableAs):
        return _create_table_as(session, stmt)
    if isinstance(stmt, ast.Insert):
        return _insert(session, stmt)
    if isinstance(stmt, ast.DropTable):
        return _drop_table(session, stmt)
    if isinstance(stmt, (ast.CreateMaterializedView,
                         ast.RefreshMaterializedView,
                         ast.DropMaterializedView)):
        # materialized views (trino_tpu/matview/): the embedded path runs
        # the REFRESH's defining query on the local executor; the
        # coordinator intercepts these statements earlier to execute the
        # refresh through its distributed path
        from trino_tpu.matview import lifecycle as mv_lifecycle

        columns, rows = mv_lifecycle.dispatch_mv_statement(
            session, stmt, sql=sql)
        return QueryResult(columns, [], rows)
    if isinstance(stmt, ast.Delete):
        return _delete(session, stmt)
    if isinstance(stmt, ast.Update):
        return _update(session, stmt)
    if isinstance(stmt, ast.CreateFunction):
        from trino_tpu.sql.routines import (
            RoutineError, UdfDef, expand_udfs, validate)

        name = stmt.name[-1].lower()
        if name in session.udfs and not stmt.or_replace:
            raise RoutineError(f"function already exists: {name}")
        # early binding: routine calls INSIDE the body expand at creation
        # (so validation sees a closed expression and later redefinitions
        # of inner routines don't change this one)
        body = expand_udfs(stmt.body, session.udfs)
        udf = UdfDef(name, tuple(stmt.params), stmt.returns, body)
        validate(udf)
        session.udfs[name] = udf
        return QueryResult(["result"], [], [("CREATE FUNCTION",)])
    if isinstance(stmt, ast.DropFunction):
        name = stmt.name[-1].lower()
        if name not in session.udfs:
            if stmt.if_exists:
                return QueryResult(["result"], [], [("DROP FUNCTION",)])
            raise ValueError(f"function not found: {name}")
        del session.udfs[name]
        return QueryResult(["result"], [], [("DROP FUNCTION",)])
    if isinstance(stmt, ast.Prepare):
        # reference: execution/PrepareTask — the statement is stored parsed;
        # parameters bind at EXECUTE time (sql/tree/Parameter)
        if not hasattr(session, "prepared_statements"):
            session.prepared_statements = {}
        session.prepared_statements[stmt.name] = stmt.statement
        return QueryResult(["result"], [], [("PREPARE",)])
    if isinstance(stmt, ast.ExecutePrepared):
        prepared = getattr(session, "prepared_statements", {}).get(stmt.name)
        if prepared is None:
            raise ValueError(f"prepared statement not found: {stmt.name}")
        bound = bind_parameters(prepared, stmt.params)
        return _dispatch_statement(session, bound)
    if isinstance(stmt, ast.Deallocate):
        store = getattr(session, "prepared_statements", {})
        if stmt.name not in store:
            raise ValueError(f"prepared statement not found: {stmt.name}")
        del store[stmt.name]
        return QueryResult(["result"], [], [("DEALLOCATE",)])
    if isinstance(stmt, ast.Call):
        return _call_procedure(session, stmt)
    if isinstance(stmt, ast.StartTransaction):
        from trino_tpu.exec import transaction as txn_mod

        txn_mod.begin(session)
        return QueryResult(["result"], [], [("START TRANSACTION",)])
    if isinstance(stmt, ast.Commit):
        txn = getattr(session, "transaction", None)
        if txn is None:
            raise ValueError("no transaction in progress")
        txn.commit()
        return QueryResult(["result"], [], [("COMMIT",)])
    if isinstance(stmt, ast.Rollback):
        txn = getattr(session, "transaction", None)
        if txn is None:
            raise ValueError("no transaction in progress")
        txn.rollback()
        return QueryResult(["result"], [], [("ROLLBACK",)])
    if isinstance(stmt, ast.SetSession):
        session.set_property(stmt.name, stmt.value)
        return QueryResult(["result"], [], [("SET SESSION",)])
    if isinstance(stmt, ast.ResetSession):
        from trino_tpu.client.properties import SYSTEM_SESSION_PROPERTIES

        meta = SYSTEM_SESSION_PROPERTIES.get(stmt.name)
        if meta is None:
            raise ValueError(f"session property '{stmt.name}' does not exist")
        if meta.default is None:
            session.properties.pop(stmt.name, None)
        else:
            session.properties[stmt.name] = meta.default
        return QueryResult(["result"], [], [("RESET SESSION",)])
    if isinstance(stmt, ast.ShowSession):
        from trino_tpu.client.properties import SYSTEM_SESSION_PROPERTIES

        rows = [
            (name, str(session.properties.get(name, meta.default)),
             str(meta.default), meta.py_type.__name__, meta.description)
            for name, meta in sorted(SYSTEM_SESSION_PROPERTIES.items())
        ]
        return QueryResult(["Name", "Value", "Default", "Type", "Description"], [], rows)
    if isinstance(stmt, ast.ShowTables):
        return _show_tables(session, stmt)
    if isinstance(stmt, ast.ShowSchemas):
        return _show_schemas(session, stmt)
    if isinstance(stmt, ast.ShowColumns):
        return _show_columns(session, stmt)
    if not isinstance(stmt, ast.Query):
        raise ValueError(f"unsupported statement {type(stmt).__name__}")
    udfs = getattr(session, "udfs", None)
    if udfs:
        from trino_tpu.sql.routines import expand_udfs

        stmt = expand_udfs(stmt, udfs)
    root = Planner(session).plan(stmt)
    root = optimize(root, session)
    # materialized-view substitution (trino_tpu/matview/): a fresh MV
    # whose definition matches a plan subtree serves as a storage scan
    from trino_tpu.matview.substitute import substitute_plan

    root, _mv_notes = substitute_plan(session, root)
    page = Executor(session).execute_checked(root)
    return QueryResult(root.column_names, page.columns, page.to_pylist())


def mv_notes_header(notes) -> str:
    """EXPLAIN header lines for the materialized-view substitution
    decisions: the scan annotation shows WHERE a view substituted; these
    lines show the freshness verdict (including fallbacks, which leave
    no mark on the plan)."""
    lines = []
    for n in notes or ():
        if n["result"] == "substituted":
            extra = (f" (prefix {n['prefix']} columns)"
                     if n.get("prefix") else "")
            lines.append(f"Materialized view {n['view']}: substituted"
                         f"{extra}")
        else:
            lines.append(f"Materialized view {n['view']}: fallback "
                         f"({n['result']}: {n['reason']})")
    return "\n".join(lines) + "\n" if lines else ""


def explain_query(session, sql, mode: str = "logical", stmt=None) -> str:
    if stmt is None:
        stmt = parse_statement(sql)
        if isinstance(stmt, ast.Explain):
            mode = stmt.mode
            stmt = stmt.statement
    root = Planner(session).plan(stmt)
    root = optimize(root, session)
    stamp_join_estimates(root, session)
    from trino_tpu.matview.substitute import substitute_plan

    root, mv_notes = substitute_plan(session, root)
    header = mv_notes_header(mv_notes)
    if mode == "distributed":
        from trino_tpu.sql.planner.fragmenter import fragment_plan, format_fragments

        return header + format_fragments(fragment_plan(root, session))
    return header + format_plan(root)


def _resolve_table_name(session, parts, write: bool = False):
    parts = [p.lower() for p in parts]
    catalog = session.properties.get("catalog", "tpch")
    schema = session.properties.get("schema", "tiny")
    if len(parts) == 3:
        catalog, schema, table = parts
    elif len(parts) == 2:
        schema, table = parts
    else:
        (table,) = parts
    if catalog not in session.catalogs:
        raise ValueError(f"catalog not found: {catalog}")
    if write:
        ac = getattr(session, "access_control", None)
        if ac is not None:
            ac.check_can_write(session.identity, catalog, schema, table)
        txn = getattr(session, "transaction", None)
        if txn is not None:
            # writes inside an explicit transaction go to its overlay
            # (exec/transaction.py; reference: TransactionManager handles)
            txn.enlist(catalog)
    return session.catalogs[catalog], schema, table


def _resolve_table_named(session, parts, write: bool = False):
    """Like _resolve_table_name but also returns the resolved CATALOG NAME
    (DML rewrites re-plan against the table and must name the same
    catalog, never re-derive it by connector identity)."""
    parts_l = [p.lower() for p in parts]
    catalog = session.properties.get("catalog", "tpch")
    if len(parts_l) == 3:
        catalog = parts_l[0]
    conn, schema, table = _resolve_table_name(session, parts, write=write)
    return conn, catalog, schema, table


def _call_procedure(session, stmt):
    """CALL catalog.schema.procedure(args...) (reference:
    execution/CallTask: resolve the procedure through connector metadata,
    evaluate constant arguments, invoke). Arguments analyze against an
    empty scope and must constant-fold — a procedure is a control-plane
    action, not a row pipeline."""
    from trino_tpu.sql.analyzer.expr_analyzer import ExprAnalyzer
    from trino_tpu.sql.analyzer.scope import Scope
    from trino_tpu.sql.planner.planner import _fold_constant

    parts = [p.lower() for p in stmt.name]
    catalog = session.properties.get("catalog", "tpch")
    schema = session.properties.get("schema", "tiny")
    if len(parts) == 3:
        catalog, schema, proc = parts
    elif len(parts) == 2:
        schema, proc = parts
    else:
        (proc,) = parts
    conn = session.catalogs.get(catalog)
    if conn is None:
        raise ValueError(f"catalog not found: {catalog}")
    fn = conn.procedure(schema, proc)
    if fn is None:
        raise ValueError(
            f"procedure not registered: {catalog}.{schema}.{proc}")
    analyzer = ExprAnalyzer(Scope([], None))
    values = []
    for e in stmt.args:
        c = _fold_constant(analyzer.analyze(e))
        if c is None:
            raise ValueError(
                f"CALL {catalog}.{schema}.{proc}: arguments must be "
                "constants")
        v = c.value
        if v is not None and c.type.is_decimal:
            v = float(v) / (10 ** c.type.scale)
        values.append(v)
    message = fn(session, *values)
    return QueryResult(["result"], [], [(message or "CALL",)])


def _create_table(session, stmt):
    """CREATE TABLE (reference: execution/CreateTableTask.java)."""
    from trino_tpu import types as T

    conn, schema, table = _resolve_table_name(session, stmt.name, write=True)
    if conn.get_table(schema, table) is not None:
        if stmt.not_exists:
            return QueryResult(["result"], [], [("CREATE TABLE",)])
        raise ValueError(f"table already exists: {schema}.{table}")
    schema_def = [(n.lower(), T.parse_type(t)) for n, t in stmt.columns]
    conn.create_table(schema, table, schema_def, [])
    return QueryResult(["result"], [], [("CREATE TABLE",)])


def _create_table_as(session, stmt):
    """CTAS (reference: the TableWriterOperator/TableFinishOperator pair,
    collapsed: the source query runs eagerly, rows sink via the connector
    write SPI — distributed scaled writers are the SPMD tier's upgrade)."""
    conn, schema, table = _resolve_table_name(session, stmt.name, write=True)
    if conn.get_table(schema, table) is not None:
        if stmt.not_exists:
            return QueryResult(["rows"], [], [(0,)])
        raise ValueError(f"table already exists: {schema}.{table}")
    root = Planner(session).plan(stmt.query)
    root = optimize(root, session)
    page = Executor(session).execute_checked(root)
    rows = page.to_pylist()
    schema_def = list(zip([n.lower() for n in root.column_names], root.source.output_types))
    conn.create_table(schema, table, schema_def, rows)
    return QueryResult(["rows"], [], [(len(rows),)])


def _insert(session, stmt):
    """INSERT INTO (reference: execution/InsertTask + page sink)."""
    conn, schema, table = _resolve_table_name(session, stmt.name, write=True)
    meta = conn.get_table(schema, table)
    if meta is None:
        raise ValueError(f"table not found: {schema}.{table}")
    root = Planner(session).plan(stmt.query)
    root = optimize(root, session)
    page = Executor(session).execute_checked(root)
    rows = page.to_pylist()
    table_cols = [c.name for c in meta.columns]
    src_width = len(root.column_names)
    if stmt.columns:
        named = [c.lower() for c in stmt.columns]
        if len(named) != src_width:
            raise ValueError("INSERT column list does not match query width")
        if len(set(named)) != len(named):
            raise ValueError("INSERT column list contains duplicates")
        for c in named:
            if c not in table_cols:
                raise ValueError(f"insert column does not exist: {c}")
        pos = {c: i for i, c in enumerate(named)}
        # unmentioned columns get NULL (reference Insert semantics)
        rows = [
            tuple(r[pos[c]] if c in pos else None for c in table_cols)
            for r in rows
        ]
    elif src_width != len(table_cols):
        raise ValueError(
            f"INSERT has {src_width} expressions but table has {len(table_cols)} columns")
    _check_insert_types(meta, stmt.columns, root.source.output_types)
    n = conn.insert_rows(schema, table, rows)
    return QueryResult(["rows"], [], [(n,)])


def _check_insert_types(meta, named_columns, src_types):
    """Reject sources that cannot widen into the target column type
    (reference: Trino's 'Insert query has mismatched column types'). A
    source type is accepted when it IS the target or implicitly coerces to
    it (common super type == target): bigint -> decimal is fine, decimal ->
    bigint is a silent-truncation hazard and is rejected."""
    from trino_tpu import types as T

    if named_columns:
        targets = [
            meta.columns[meta.column_index(c.lower())].type for c in named_columns
        ]
    else:
        targets = [c.type for c in meta.columns]
    for i, (src, tgt) in enumerate(zip(src_types, targets)):
        if src == tgt or src == T.UNKNOWN:
            continue
        # the reference's implicit-coercion rule (TypeCoercion.canCoerce):
        # src must widen EXACTLY into tgt — common super type IS the target,
        # or an integer fits the decimal's integral digits
        if T.common_super_type(src, tgt) == tgt:
            continue
        int_digits = {T.INTEGER: 10, T.BIGINT: 19}.get(src)
        if (int_digits is not None and tgt.is_decimal
                and tgt.precision - tgt.scale >= int_digits):
            continue
        raise ValueError(
            f"insert column {i}: mismatched types — query produces {src}, "
            f"table expects {tgt}")


def _delete(session, stmt):
    """DELETE FROM t [WHERE p]: rows where p IS TRUE are removed; the KEPT
    set (NOT p OR p IS NULL) is computed by the engine and the table
    overwritten (reference: sql/tree/Delete; the whole-table rewrite is
    the simple-connector analog of the row-change/merge machinery)."""
    conn, catalog, schema, table = _resolve_table_named(
        session, stmt.name, write=True)
    meta = conn.get_table(schema, table)
    if meta is None:
        raise ValueError(f"table not found: {schema}.{table}")
    total = conn.table_row_count(schema, table)
    if total is None:  # stats are optional SPI surface: count via the engine
        total = _dml_select_rows(session, catalog, schema, table, meta,
                                 count_only=True)
    if stmt.where is None:
        kept = []
    else:
        keep_pred = ast.LogicalBinary(
            "or", ast.Not(stmt.where), ast.IsNull(stmt.where))
        kept = _dml_select_rows(session, catalog, schema, table, meta,
                                where=keep_pred)
    conn.overwrite_rows(schema, table, kept)
    return QueryResult(["rows"], [], [(total - len(kept),)])


def _update(session, stmt):
    """UPDATE t SET c = e [WHERE p]: every row rewrites as
    CASE WHEN p THEN e ELSE c END per assigned column (reference:
    sql/tree/Update). Assignment types must COERCE to the column type
    (widening only), matching INSERT's check."""
    from trino_tpu import types as T
    from trino_tpu.sql.analyzer.expr_analyzer import ExprAnalyzer
    from trino_tpu.sql.analyzer.scope import Field, Scope

    conn, catalog, schema, table = _resolve_table_named(
        session, stmt.name, write=True)
    meta = conn.get_table(schema, table)
    if meta is None:
        raise ValueError(f"table not found: {schema}.{table}")
    assigns = {c.lower(): e for c, e in stmt.assignments}
    col_types = {m.name: m.type for m in meta.columns}
    scope = Scope([Field(m.name, m.type, table) for m in meta.columns], None)
    analyzer = ExprAnalyzer(scope)
    for c, e in assigns.items():
        if c not in col_types:
            raise ValueError(f"update column does not exist: {c}")
        et = analyzer.analyze(e).type
        target = col_types[c]
        if et == T.UNKNOWN or T.common_super_type(et, target) == target:
            continue
        if et.is_decimal and target.is_decimal:
            # store-assignment (SQL): decimal precision may NARROW — the
            # cast's runtime DECIMAL_OVERFLOW check protects values that
            # do not fit (amt = amt * 2 grows the static precision even
            # though the values usually still fit)
            continue
        raise ValueError(
            f"UPDATE assignment to {c}: {et} does not coerce to {target}")
    # ONE scan computes the rewritten rows AND the match count (an extra
    # boolean column, stripped before the overwrite)
    rows = _dml_select_rows(session, catalog, schema, table, meta,
                            assigns=assigns, assign_where=stmt.where,
                            with_match_flag=stmt.where is not None)
    if stmt.where is None:
        updated = len(rows)
    else:
        updated = sum(1 for r in rows if r[-1])
        rows = [r[:-1] for r in rows]
    conn.overwrite_rows(schema, table, rows)
    return QueryResult(["rows"], [], [(updated,)])


def _dml_select_rows(session, catalog, schema, table, meta, where=None,
                     assigns=None, assign_where=None, count_only=False,
                     with_match_flag=False):
    """Evaluate a rewrite SELECT built at the AST level over the target
    table with the engine's full expression machinery: the kept rows of a
    DELETE, the updated projection of an UPDATE (plus an optional
    predicate-match flag column), or a row count."""
    table_rel = ast.Table((catalog, schema, table))
    if count_only:
        items = (ast.SelectItem(
            ast.FunctionCall("count", (), is_star=True), "c"),)
    else:
        items = []
        for cm in meta.columns:
            col = ast.Identifier((cm.name,))
            e = col
            if assigns and cm.name in assigns:
                e = (assigns[cm.name] if assign_where is None
                     else ast.SearchedCase(((assign_where, assigns[cm.name]),), col))
                e = ast.Cast(e, str(cm.type))  # keep the column's type
            items.append(ast.SelectItem(e, cm.name))
        if with_match_flag and assign_where is not None:
            items.append(ast.SelectItem(
                ast.SearchedCase(
                    ((assign_where, ast.Literal("boolean", True)),),
                    ast.Literal("boolean", False)), "__match"))
        items = tuple(items)
    q = ast.Query(body=ast.QuerySpec(
        select_items=items, distinct=False, from_=table_rel, where=where,
        group_by=(), having=None))
    root = Planner(session).plan(q)
    root = optimize(root, session)
    page = Executor(session).execute_checked(root)
    rows = page.to_pylist()
    return rows[0][0] if count_only else rows


def _drop_table(session, stmt):
    conn, schema, table = _resolve_table_name(session, stmt.name, write=True)
    if conn.get_table(schema, table) is None:
        if stmt.if_exists:
            return QueryResult(["result"], [], [("DROP TABLE",)])
        raise ValueError(f"table not found: {schema}.{table}")
    conn.drop_table(schema, table)
    return QueryResult(["result"], [], [("DROP TABLE",)])


def explain_analyze(session, stmt, verbose: bool = False) -> str:
    """EXPLAIN ANALYZE: execute, then print the plan annotated with the
    executor's per-operator stats (reference: ExplainAnalyzeOperator +
    PlanPrinter.java:183 with OperatorStats injected). The header's wall
    time covers planning AND execution, broken down so it agrees with the
    query-level span totals (plan/optimize time used to be silently
    dropped). ``verbose`` adds device detail: per-node bytes/peaks plus the
    compiled tier's compile-cache disposition over this run."""
    import time as _time

    from trino_tpu.obs import metrics as M

    t_plan = _time.perf_counter()
    root = Planner(session).plan(stmt)
    root = optimize(root, session)
    stamp_join_estimates(root, session)
    from trino_tpu.matview.substitute import substitute_plan

    root, mv_notes = substitute_plan(session, root)
    plan_s = _time.perf_counter() - t_plan
    ex = Executor(session)
    hits0, misses0 = (M.COMPILE_CACHE_HITS.value(),
                      M.COMPILE_CACHE_MISSES.value())
    t0 = _time.perf_counter()
    ex.execute_checked(root)
    exec_s = _time.perf_counter() - t0
    from trino_tpu.exec.operator_stats import wall_time_header

    header = [wall_time_header(plan_s, exec_s)]
    if ex.memory.budget is not None:
        header.append(
            f"Device memory budget: {ex.memory.budget // 1024}KiB,"
            f" peak working set: {ex.memory.peak // 1024}KiB,"
            f" spills: {len(ex.memory.spills)}"
        )
    else:
        header.append(f"Peak working set: {ex.memory.peak // 1024}KiB (no budget)")
    if verbose:
        # the compile-cache delta is PROCESS-WIDE over this run's window
        # (the registry has no per-query partitions): labeled as such so
        # concurrent compiled-tier activity is never misread as this query
        staged = sum(ex.scan_stats.values())
        header.append(
            f"Device detail: staged rows={staged},"
            f" compile cache hits/misses (process-wide during run)="
            f"{int(M.COMPILE_CACHE_HITS.value() - hits0)}/"
            f"{int(M.COMPILE_CACHE_MISSES.value() - misses0)},"
            f" dynamic-filter host seconds={ex.df_apply_s * 1e3:.1f}ms")
    mv_header = mv_notes_header(mv_notes)
    return mv_header + "\n".join(header) + "\n" + format_plan(
        root, executor=ex, verbose=verbose)


def _show_tables(session, stmt):
    if stmt.schema:
        parts = stmt.schema
        catalog = parts[0] if len(parts) == 2 else session.properties.get("catalog", "tpch")
        schema = parts[-1]
    else:
        catalog = session.properties.get("catalog", "tpch")
        schema = session.properties.get("schema", "tiny")
    conn = session.catalogs[catalog]
    rows = [(t,) for t in conn.list_tables(schema)]
    return QueryResult(["Table"], [], rows)


def _show_schemas(session, stmt):
    catalog = stmt.catalog or session.properties.get("catalog", "tpch")
    conn = session.catalogs[catalog]
    return QueryResult(["Schema"], [], [(s,) for s in conn.list_schemas()])


def _show_columns(session, stmt):
    parts = [p.lower() for p in stmt.table]
    catalog = session.properties.get("catalog", "tpch")
    schema = session.properties.get("schema", "tiny")
    if len(parts) == 3:
        catalog, schema, table = parts
    elif len(parts) == 2:
        schema, table = parts
    else:
        (table,) = parts
    meta = session.catalogs[catalog].get_table(schema, table)
    if meta is None:
        raise ValueError(f"table not found: {table}")
    return QueryResult(
        ["Column", "Type"], [], [(c.name, str(c.type)) for c in meta.columns]
    )
