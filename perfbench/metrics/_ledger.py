"""The program's compile ledger (``compile_stats.compile_ledger_snapshot``),
read when the run's metrics are: process start to the end of the run.  The
window compiles, lowers and traces nothing (``window_compiles`` reads 0), so
what it holds is set-up's.  None where the program keeps no ledger."""


def ledger_rows():
    try:
        from kaminpar_tpu.utils.compile_stats import compile_ledger_snapshot
    except ImportError:
        return None
    return compile_ledger_snapshot()


def ledger_sum(*fields):
    rows = ledger_rows()
    if rows is None:
        return None
    return sum(row[f] for row in rows for f in fields)


def by_program(rows) -> list:
    """[(program, {field: total over its phases})], most seconds first."""
    out: dict = {}
    for row in rows:
        acc = out.setdefault(row["program"], {})
        for key, value in row.items():
            if key not in ("program", "phase"):
                acc[key] = acc.get(key, 0) + value
    return sorted(
        out.items(),
        key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                         + kv[1]["compile_s"] + kv[1]["load_s"]),
    )
