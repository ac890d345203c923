"""The queries of the cell `tpcds-q-mix`, as a user of the library runs
them, and the comparison that decides whether an answer is the
reference's (beside `sales_queries.py` and `bid_queries.py`, for the
same reason: `chipbench/system.py::DeltaTpu` knows nothing of SQL). A
driver takes `open_catalog` and `run_query` of the system it was given
where that system has its own (the tests' broken systems) and these
otherwise.

The texts are TPC-DS v2.4's, verbatim from `benchmarks/tpcds_queries.py`
(upstream's `TPCDSBenchmarkQueries`; a test holds them equal). Beside
each: the kind of every output column, which says how it is compared,
and the `ORDER BY` as (output column, descending).

- `exact`: keys, strings and counts, equal (an integer column with a
  null in it comes out of the engine as a float: 7003001.0 equals
  7003001);
- `cents`: a sum of a `decimal(7,2)` column, equal to the cent: the
  engine's float, times 100 and rounded, against the reference's integer
  of cents;
- `avg`, `avg_cents`: an average, within `AVG_RELATIVE` of the
  reference's (`avg_cents`: of a money column, which the reference has
  turned back into units).
"""

from __future__ import annotations

import dataclasses
import math

# float64 summation order: the engine's pandas and device paths and the
# reference's SQLite each add a group's ~10^2..10^3 values in another
# order, and each addition rounds at 2^-53 relative; a float32 sum is
# seven orders of magnitude outside it
AVG_RELATIVE = 1e-9
LIMIT = 100


@dataclasses.dataclass(frozen=True)
class Query:
    name: str
    text: str
    kinds: tuple
    order: tuple    # (output column, descending) of the ORDER BY


QUERIES = {}


def _query(name, kinds, order, text):
    QUERIES[name] = Query(name, text, kinds, order)


_query("q3", ("exact", "exact", "exact", "cents"),
       ((0, False), (3, True), (1, False)), """
select  dt.d_year
       ,item.i_brand_id brand_id
       ,item.i_brand brand
       ,sum(ss_sales_price) sum_agg
 from  date_dim dt
      ,store_sales
      ,item
 where dt.d_date_sk = store_sales.ss_sold_date_sk
   and store_sales.ss_item_sk = item.i_item_sk
   and item.i_manufact_id = 816
   and dt.d_moy=11
 group by dt.d_year
      ,item.i_brand
      ,item.i_brand_id
 order by dt.d_year
         ,sum_agg desc
         ,brand_id
 limit 100
""")

_query("q7", ("exact", "avg", "avg_cents", "avg_cents", "avg_cents"),
       ((0, False),), """
select  i_item_id,
        avg(ss_quantity) agg1,
        avg(ss_list_price) agg2,
        avg(ss_coupon_amt) agg3,
        avg(ss_sales_price) agg4
 from store_sales, customer_demographics, date_dim, item, promotion
 where ss_sold_date_sk = d_date_sk and
       ss_item_sk = i_item_sk and
       ss_cdemo_sk = cd_demo_sk and
       ss_promo_sk = p_promo_sk and
       cd_gender = 'F' and
       cd_marital_status = 'W' and
       cd_education_status = 'College' and
       (p_channel_email = 'N' or p_channel_event = 'N') and
       d_year = 2001
 group by i_item_id
 order by i_item_id
 limit 100
""")

_query("q19", ("exact", "exact", "exact", "exact", "cents"),
       ((4, True), (1, False), (0, False), (2, False), (3, False)), """
select  i_brand_id brand_id, i_brand brand, i_manufact_id, i_manufact,
 	sum(ss_ext_sales_price) ext_price
 from date_dim, store_sales, item,customer,customer_address,store
 where d_date_sk = ss_sold_date_sk
   and ss_item_sk = i_item_sk
   and i_manager_id=26
   and d_moy=12
   and d_year=2000
   and ss_customer_sk = c_customer_sk
   and c_current_addr_sk = ca_address_sk
   and substr(ca_zip,1,5) <> substr(s_zip,1,5)
   and ss_store_sk = s_store_sk
 group by i_brand
      ,i_brand_id
      ,i_manufact_id
      ,i_manufact
 order by ext_price desc
         ,i_brand
         ,i_brand_id
         ,i_manufact_id
         ,i_manufact
limit 100 
""")

_query("q68", ("exact", "exact", "exact", "exact", "exact", "cents", "cents", "cents"),
       ((0, False), (4, False)), """
select  c_last_name
       ,c_first_name
       ,ca_city
       ,bought_city
       ,ss_ticket_number
       ,extended_price
       ,extended_tax
       ,list_price
 from (select ss_ticket_number
             ,ss_customer_sk
             ,ca_city bought_city
             ,sum(ss_ext_sales_price) extended_price
             ,sum(ss_ext_list_price) list_price
             ,sum(ss_ext_tax) extended_tax
       from store_sales
           ,date_dim
           ,store
           ,household_demographics
           ,customer_address
       where store_sales.ss_sold_date_sk = date_dim.d_date_sk
         and store_sales.ss_store_sk = store.s_store_sk
        and store_sales.ss_hdemo_sk = household_demographics.hd_demo_sk
        and store_sales.ss_addr_sk = customer_address.ca_address_sk
        and date_dim.d_dom between 1 and 2
        and (household_demographics.hd_dep_count = 1 or
             household_demographics.hd_vehicle_count= -1)
        and date_dim.d_year in (1998,1998+1,1998+2)
        and store.s_city in ('Bethel','Summit')
       group by ss_ticket_number
               ,ss_customer_sk
               ,ss_addr_sk,ca_city) dn
      ,customer
      ,customer_address current_addr
 where ss_customer_sk = c_customer_sk
   and customer.c_current_addr_sk = current_addr.ca_address_sk
   and current_addr.ca_city <> bought_city
 order by c_last_name
         ,ss_ticket_number
 limit 100
""")

_query("q96", ("exact",),
       ((0, False),), """
select  count(*)
from store_sales
    ,household_demographics
    ,time_dim, store
where ss_sold_time_sk = time_dim.t_time_sk
    and ss_hdemo_sk = household_demographics.hd_demo_sk
    and ss_store_sk = s_store_sk
    and time_dim.t_hour = 16
    and time_dim.t_minute >= 30
    and household_demographics.hd_dep_count = 4
    and store.s_store_name = 'ese'
order by count(*)
limit 100
""")

_query("q42", ("exact", "exact", "exact", "cents"),
       ((3, True), (0, False), (1, False), (2, False)), """
select  dt.d_year
 	,item.i_category_id
 	,item.i_category
 	,sum(ss_ext_sales_price)
 from 	date_dim dt
 	,store_sales
 	,item
 where dt.d_date_sk = store_sales.ss_sold_date_sk
 	and store_sales.ss_item_sk = item.i_item_sk
 	and item.i_manager_id = 1
 	and dt.d_moy=11
 	and dt.d_year=2002
 group by 	dt.d_year
 		,item.i_category_id
 		,item.i_category
 order by       sum(ss_ext_sales_price) desc,dt.d_year
 		,item.i_category_id
 		,item.i_category
limit 100 
""")

_query("q52", ("exact", "exact", "exact", "cents"),
       ((0, False), (3, True), (1, False)), """
select  dt.d_year
 	,item.i_brand_id brand_id
 	,item.i_brand brand
 	,sum(ss_ext_sales_price) ext_price
 from date_dim dt
     ,store_sales
     ,item
 where dt.d_date_sk = store_sales.ss_sold_date_sk
    and store_sales.ss_item_sk = item.i_item_sk
    and item.i_manager_id = 1
    and dt.d_moy=11
    and dt.d_year=2001
 group by dt.d_year
 	,item.i_brand
 	,item.i_brand_id
 order by dt.d_year
 	,ext_price desc
 	,brand_id
limit 100 
""")

_query("q55", ("exact", "exact", "cents"),
       ((2, True), (0, False)), """
select  i_brand_id brand_id, i_brand brand,
 	sum(ss_ext_sales_price) ext_price
 from date_dim, store_sales, item
 where d_date_sk = ss_sold_date_sk
 	and ss_item_sk = i_item_sk
 	and i_manager_id=87
 	and d_moy=11
 	and d_year=2001
 group by i_brand, i_brand_id
 order by ext_price desc, i_brand_id
limit 100 
""")



def open_catalog(root: str, table_paths: dict):
    """A catalog on the library's default engine with the ten tables
    registered; every table's newest snapshot is taken once, here."""
    from delta_tpu.catalog import Catalog

    catalog = Catalog(root)
    for name, path in table_paths.items():
        catalog.register(name, path).latest_snapshot()
    return catalog


def run_query(catalog, query: Query) -> list:
    """The query's rows as Python tuples: the text as it is written,
    its `LIMIT` with it."""
    from delta_tpu.sqlengine import execute_select

    answer = execute_select(query.text, catalog=catalog, name=query.name)
    return list(zip(*(column.to_pylist() for column in answer.columns))) \
        if answer.num_columns else []


def same_value(got, want, kind: str) -> bool:
    if got is None or want is None:
        return got is None and want is None
    if isinstance(got, float) and math.isnan(got):
        return False
    if kind == "cents":
        return round(got * 100) == want
    if kind in ("avg", "avg_cents"):
        return abs(got - want) <= AVG_RELATIVE * abs(want)
    return got == want


def same_row(got: tuple, want: tuple, kinds: tuple) -> bool:
    return len(got) == len(want) and all(
        same_value(g, w, k) for g, w, k in zip(got, want, kinds))


def broken_rows(got: list, want: list, query: Query) -> int:
    """How many of the engine's rows break a guarantee, against the
    reference's rows `want` (all of them, in order, no `LIMIT`): a row
    has to be one of the reference's, each at most once, and row `i` has
    to be one of those that tie with the reference's row `i` on every
    `ORDER BY` key, so the first `LIMIT` rows are the reference's first
    and a tie at the cut may fall either way. A missing or surplus row
    counts too."""
    expected = min(LIMIT, len(want))
    keys = [c for c, _descending in query.order]
    key_kinds = tuple(query.kinds[c] for c in keys)

    def key(row):
        return tuple(row[c] for c in keys)

    broken = abs(len(got) - expected)
    start = 0
    while start < min(len(got), expected):
        stop = start     # the reference's run of rows that tie with `start`
        while stop < len(want) and same_row(key(want[stop]), key(want[start]),
                                            ("exact",) * len(keys)):
            stop += 1
        free = list(want[start:stop])
        upto = min(stop, len(got), expected)
        for row in got[start:upto]:
            match = next((i for i, w in enumerate(free)
                          if same_row(key(row), key(w), key_kinds)
                          and same_row(row, w, query.kinds)), None)
            if match is None:
                broken += 1
            else:
                del free[match]
        start = stop
    return broken
