"""The queries of the cell over a table of TPC-DS `store_sales`, as a
user of the library writes them (beside `bid_queries.py`, for the same
reason: `chipbench/system.py::DeltaTpu.plan` is fixed to the column
`x`). A driver takes `plan_sales` of the system it was given where that
system has one of its own (the tests' broken systems) and this one
otherwise."""

from __future__ import annotations


def bucket_predicate(q_lo: int, q_hi: int, p: int, c: int, w: int):
    """One of Query 28's six buckets (`query28.tpl`), the numbers as
    the template substitutes them: `ss_quantity BETWEEN q_lo AND q_hi
    AND (ss_list_price BETWEEN p AND p+10 OR ss_coupon_amt BETWEEN c AND
    c+1000 OR ss_wholesale_cost BETWEEN w AND w+20)`."""
    from delta_tpu.expressions import col, lit

    def between(name, lo, hi):
        return (col(name) >= lit(lo)) & (col(name) <= lit(hi))

    return between("ss_quantity", q_lo, q_hi) & (
        between("ss_list_price", p, p + 10)
        | between("ss_coupon_amt", c, c + 1000)
        | between("ss_wholesale_cost", w, w + 20))


def window_predicate(day_lo: int, day_hi: int):
    from delta_tpu.expressions import col, lit

    return ((col("ss_sold_date_sk") >= lit(day_lo))
            & (col("ss_sold_date_sk") <= lit(day_hi)))


def plan_sales(snapshot, day_lo: int, day_hi: int, bucket=None) -> list:
    """Paths of the files a scan of the sold dates `day_lo..day_hi`
    (`ss_sold_date_sk`, both ends in) and, where `bucket` is `(q_lo,
    q_hi, p, c, w)`, that bucket of Query 28 has to read; where it is a list of
    them, any of them (the query asks its six side by side)."""
    pred = window_predicate(day_lo, day_hi)
    if bucket is not None:
        either = None
        for one in ([bucket] if isinstance(bucket, tuple) else bucket):
            one = bucket_predicate(*one)
            either = one if either is None else either | one
        pred = pred & either
    return snapshot.scan(filter=pred).file_paths()
