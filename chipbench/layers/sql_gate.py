"""The window's decisions of the `sql` gate that priced an operator:
every record but the per-query one (`op=query`), which binds no
execution. Beside `sql_device_rows_pct.py` and `sql_fallback_pct.py`,
which read it."""


def operators(run):
    return [r for r in run.gates if r["gate"] == "sql"
            and r.get("inputs", {}).get("op") != "query"]
