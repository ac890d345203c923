"""Device dispatch funnel: host-to-device bytes of the window's
`sqlops.*` and `sql.operand_upload` dispatch records, per pass: what the
SQL operators weigh on the link (a warm operand cache ships a join's
probe side alone). None where no SQL operator reached the chip."""


def read(run):
    mine = [r for r in run.dispatches if r["kernel"].startswith("sqlops.")
            or r["kernel"] == "sql.operand_upload"]
    if not mine:
        return None
    return sum(r["h2d_bytes"] for r in mine) / 1e6 / len(run.ops)
