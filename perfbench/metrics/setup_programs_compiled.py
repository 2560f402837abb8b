"""Compile layer: programs compiled in set-up because the persistent cache
missed them (compile ledger ``compiles``).  Also prints, before the result
line, ``setup-programs:`` and a JSON list of the ten programs with the most
set-up seconds: [program, trace_s, lower_s, compile_s, load_s, compiles,
loads]."""
import json

from perfbench.metrics._ledger import by_program, ledger_rows

_FIELDS = ("trace_s", "lower_s", "compile_s", "load_s", "compiles", "loads")


def read(run):
    rows = ledger_rows()
    if rows is None:
        return None
    top = [[name] + [acc[f] for f in _FIELDS] for name, acc in by_program(rows)[:10]]
    print("setup-programs: " + json.dumps(top), flush=True)
    return sum(row["compiles"] for row in rows)
