"""Generator of the configuration `tpcds-sf1`: the ten tables of TPC-DS
that the cell's eight queries touch, at the specification's row counts
for scale factor 1, with every column of each table in the
specification's order and type, loaded as upstream's `TPCDSDataLoad`
loads dsdgen's output: each table one commit of this library's writer
(`delta_tpu.api.write_table`), `store_sales` partitioned by
`ss_sold_date_sk` with its null partition kept, the other nine
unpartitioned.

dsdgen is not redistributable and there is no network here, so the
values are made from the seed, recalled from the specification
(`configs/tpcds-sf1.json`, `assumed`): a key of a dimension is 1..rows
(`d_date_sk` from 2,415,022, `t_time_sk` from 0), `customer_demographics`
and `household_demographics` are the cross products the specification
makes them, a ticket of `store_sales` is one customer's visit of about
twelve items priced as dsdgen prices them, and about 4% of every
nullable column is null. Where a column carries a constant that one of
the eight queries filters by, its values are drawn from a pool that
starts with those constants (`FIRST`), so that a table cut to a test's
size still answers every query with rows; at scale factor 1 the pools
are the specification's whole domains.

Self-contained: numpy and pyarrow, and the writer under test. The
manifest keeps the Arrow tables for the reference, which loads the
columns the queries name and lets them go (`Manifest.release`).
"""

from __future__ import annotations

import dataclasses
import datetime
import glob
import os

import numpy as np
import pyarrow as pa

FIRST_DATE = datetime.date(1900, 1, 2)
FIRST_DATE_SK = 2_415_022           # d_date_sk of 1900-01-02
FIRST_SOLD_SK = 2_450_816           # 1998-01-02
SOLD_DATES = 1_823                  # .. 2002-12-29
NULL_SHARE = 0.04
TICKET_ITEMS = 23                   # a ticket holds 1..23 rows, 12 on average
QUANTITY = 100                      # ss_quantity 1 .. 100
UNIX_EPOCH = datetime.date(1970, 1, 1)

# scale factor 1, by the specification's table of row counts
SF1_ROWS = {"store_sales": 2_880_404, "date_dim": 73_049,
            "time_dim": 86_400, "item": 18_000, "customer": 100_000,
            "customer_address": 50_000, "customer_demographics": 1_920_800,
            "household_demographics": 7_200, "store": 12, "promotion": 300}

I, L, S, D = "integer", "long", "string", "date"
M72, M52, M152 = "decimal(7,2)", "decimal(5,2)", "decimal(15,2)"

# (column, Delta type) in the specification's order; the first column of
# a dimension is its surrogate key and never null, as ss_item_sk and
# ss_ticket_number (the fact table's primary key) are
SCHEMAS = {
    "store_sales": (
        ("ss_sold_date_sk", I), ("ss_sold_time_sk", I), ("ss_item_sk", I),
        ("ss_customer_sk", I), ("ss_cdemo_sk", I), ("ss_hdemo_sk", I),
        ("ss_addr_sk", I), ("ss_store_sk", I), ("ss_promo_sk", I),
        ("ss_ticket_number", L), ("ss_quantity", I),
        ("ss_wholesale_cost", M72), ("ss_list_price", M72),
        ("ss_sales_price", M72), ("ss_ext_discount_amt", M72),
        ("ss_ext_sales_price", M72), ("ss_ext_wholesale_cost", M72),
        ("ss_ext_list_price", M72), ("ss_ext_tax", M72),
        ("ss_coupon_amt", M72), ("ss_net_paid", M72),
        ("ss_net_paid_inc_tax", M72), ("ss_net_profit", M72)),
    "date_dim": (
        ("d_date_sk", I), ("d_date_id", S), ("d_date", D),
        ("d_month_seq", I), ("d_week_seq", I), ("d_quarter_seq", I),
        ("d_year", I), ("d_dow", I), ("d_moy", I), ("d_dom", I),
        ("d_qoy", I), ("d_fy_year", I), ("d_fy_quarter_seq", I),
        ("d_fy_week_seq", I), ("d_day_name", S), ("d_quarter_name", S),
        ("d_holiday", S), ("d_weekend", S), ("d_following_holiday", S),
        ("d_first_dom", I), ("d_last_dom", I), ("d_same_day_ly", I),
        ("d_same_day_lq", I), ("d_current_day", S), ("d_current_week", S),
        ("d_current_month", S), ("d_current_quarter", S),
        ("d_current_year", S)),
    "time_dim": (
        ("t_time_sk", I), ("t_time_id", S), ("t_time", I), ("t_hour", I),
        ("t_minute", I), ("t_second", I), ("t_am_pm", S), ("t_shift", S),
        ("t_sub_shift", S), ("t_meal_time", S)),
    "item": (
        ("i_item_sk", I), ("i_item_id", S), ("i_rec_start_date", D),
        ("i_rec_end_date", D), ("i_item_desc", S), ("i_current_price", M72),
        ("i_wholesale_cost", M72), ("i_brand_id", I), ("i_brand", S),
        ("i_class_id", I), ("i_class", S), ("i_category_id", I),
        ("i_category", S), ("i_manufact_id", I), ("i_manufact", S),
        ("i_size", S), ("i_formulation", S), ("i_color", S), ("i_units", S),
        ("i_container", S), ("i_manager_id", I), ("i_product_name", S)),
    "customer": (
        ("c_customer_sk", I), ("c_customer_id", S),
        ("c_current_cdemo_sk", I), ("c_current_hdemo_sk", I),
        ("c_current_addr_sk", I), ("c_first_shipto_date_sk", I),
        ("c_first_sales_date_sk", I), ("c_salutation", S),
        ("c_first_name", S), ("c_last_name", S),
        ("c_preferred_cust_flag", S), ("c_birth_day", I),
        ("c_birth_month", I), ("c_birth_year", I), ("c_birth_country", S),
        ("c_login", S), ("c_email_address", S), ("c_last_review_date", S)),
    "customer_address": (
        ("ca_address_sk", I), ("ca_address_id", S), ("ca_street_number", S),
        ("ca_street_name", S), ("ca_street_type", S), ("ca_suite_number", S),
        ("ca_city", S), ("ca_county", S), ("ca_state", S), ("ca_zip", S),
        ("ca_country", S), ("ca_gmt_offset", M52), ("ca_location_type", S)),
    "customer_demographics": (
        ("cd_demo_sk", I), ("cd_gender", S), ("cd_marital_status", S),
        ("cd_education_status", S), ("cd_purchase_estimate", I),
        ("cd_credit_rating", S), ("cd_dep_count", I),
        ("cd_dep_employed_count", I), ("cd_dep_college_count", I)),
    "household_demographics": (
        ("hd_demo_sk", I), ("hd_income_band_sk", I), ("hd_buy_potential", S),
        ("hd_dep_count", I), ("hd_vehicle_count", I)),
    "store": (
        ("s_store_sk", I), ("s_store_id", S), ("s_rec_start_date", D),
        ("s_rec_end_date", D), ("s_closed_date_sk", I), ("s_store_name", S),
        ("s_number_employees", I), ("s_floor_space", I), ("s_hours", S),
        ("s_manager", S), ("s_market_id", I), ("s_geography_class", S),
        ("s_market_desc", S), ("s_market_manager", S), ("s_division_id", I),
        ("s_division_name", S), ("s_company_id", I), ("s_company_name", S),
        ("s_street_number", S), ("s_street_name", S), ("s_street_type", S),
        ("s_suite_number", S), ("s_city", S), ("s_county", S),
        ("s_state", S), ("s_zip", S), ("s_country", S),
        ("s_gmt_offset", M52), ("s_tax_precentage", M52)),
    "promotion": (
        ("p_promo_sk", I), ("p_promo_id", S), ("p_start_date_sk", I),
        ("p_end_date_sk", I), ("p_item_sk", I), ("p_cost", M152),
        ("p_response_target", I), ("p_promo_name", S),
        ("p_channel_dmail", S), ("p_channel_email", S),
        ("p_channel_catalog", S), ("p_channel_tv", S),
        ("p_channel_radio", S), ("p_channel_press", S),
        ("p_channel_event", S), ("p_channel_demo", S),
        ("p_channel_details", S), ("p_purpose", S),
        ("p_discount_active", S)),
}
NEVER_NULL = {"ss_item_sk", "ss_ticket_number"} | {
    columns[0][0] for name, columns in SCHEMAS.items()
    if name != "store_sales"}
PARTITION_BY = {"store_sales": ["ss_sold_date_sk"]}
MONEY = tuple(name for name, kind in SCHEMAS["store_sales"] if kind == M72)

# the constants the cell's eight queries filter by come first in the pool
# their column draws from; the rest of the pool is the specification's
FIRST = {"i_manufact_id": (816,), "i_manager_id": (1, 26, 87),
         "s_store_name": ("ese",), "s_city": ("Bethel", "Summit")}
SYLLABLES = ("ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "bar")
CITIES = ("Midway", "Fairview", "Oak Grove", "Five Points", "Pleasant Hill",
          "Centerville", "Riverside", "Liberty", "Salem", "Oakland",
          "Mount Zion", "Greenwood", "Union", "Bethel", "Summit",
          "Spring Hill", "Shiloh", "Lakeside", "Glendale", "Marion",
          "Franklin", "Springfield", "Clinton", "Georgetown", "Newport",
          "Concord", "Lebanon", "Jackson", "Ashland", "Kingston", "Oakdale",
          "Antioch", "Hopewell", "Waterloo", "Sulphur Springs", "Woodville",
          "Highland Park", "Friendship", "Pine Grove", "Enterprise")
COUNTIES = ("Williamson County", "Ziebach County", "Walker County",
            "Daviess County", "Fairfield County", "Barrow County",
            "Franklin Parish", "Luce County", "Mobile County", "Bronx County",
            "Richland County", "Huron County")
STATES = ("TN", "SD", "AL", "GA", "OH", "TX", "KY", "MI", "IL", "VA", "NE",
          "IA", "IN", "MN", "CA", "WA", "NY", "FL")
STREETS = ("Main", "Oak", "Park", "Elm", "Maple", "Cedar", "Lake", "Hill",
           "Walnut", "Spring", "Ridge", "Church", "Mill", "River", "Sunset",
           "Railroad", "Jackson", "Lincoln", "Adams", "Smith")
STREET_TYPES = ("Street", "Ave", "Blvd", "Road", "Ln", "Dr.", "Ct.", "Way",
                "Pkwy", "Cir.", "Wy", "RD", "ST", "Boulevard", "Lane",
                "Drive", "Court", "Parkway", "Circle", "Avenue")
FIRST_NAMES = ("James", "Mary", "John", "Patricia", "Robert", "Linda",
               "Michael", "Barbara", "William", "Elizabeth", "David",
               "Jennifer", "Richard", "Maria", "Charles", "Susan", "Joseph",
               "Margaret", "Thomas", "Dorothy", "Daniel", "Lisa", "Paul",
               "Nancy", "Mark", "Karen", "Donald", "Betty", "George", "Helen")
LAST_NAMES = ("Smith", "Johnson", "Williams", "Jones", "Brown", "Davis",
              "Miller", "Wilson", "Moore", "Taylor", "Anderson", "Thomas",
              "Jackson", "White", "Harris", "Martin", "Thompson", "Garcia",
              "Martinez", "Robinson", "Clark", "Rodriguez", "Lewis", "Lee",
              "Walker", "Hall", "Allen", "Young", "Hernandez", "King",
              "Wright", "Lopez", "Hill", "Scott", "Green", "Adams", "Baker",
              "Gonzalez", "Nelson", "Carter")
SALUTATIONS = ("Mr.", "Mrs.", "Ms.", "Miss", "Dr.", "Sir")
COUNTRIES = ("UNITED STATES", "CANADA", "MEXICO", "JAPAN", "GERMANY", "FRANCE",
             "BRAZIL", "INDIA", "CHINA", "ITALY", "SPAIN", "CHILE", "PERU")
CATEGORIES = ("Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics")
CLASSES = ("dresses", "shirts", "infants", "athletic", "classical",
           "bracelets", "bedding", "football", "romance", "portable",
           "pants", "accessories", "newborn", "mens", "pop", "earings")
BRANDS = ("amalg", "edu pack", "exporti", "importo", "scholar", "univ",
          "corp", "brand", "maxi", "nameless")
COLORS = ("red", "blue", "green", "white", "black", "pink", "peru", "navy",
          "khaki", "plum", "rose", "snow", "tan", "wheat", "ivory", "linen")
SIZES = ("petite", "small", "medium", "large", "extra large", "economy", "N/A")
UNITS = ("Each", "Dozen", "Case", "Pound", "Ounce", "Bunch", "Box", "Gross",
         "Lb", "Oz", "Tbl", "Tsp", "Cup", "Pallet", "Ton", "Carton", "Dram",
         "Gram", "N/A", "Unknown")
EDUCATION = ("Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
             "Advanced Degree", "Unknown")
MARITAL = ("M", "S", "D", "W", "U")
CREDIT = ("Good", "High Risk", "Low Risk", "Unknown")
BUY_POTENTIAL = ("0-500", "501-1000", "1001-5000", "5001-10000", ">10000",
                 "Unknown")
DAY_NAMES = ("Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
             "Saturday", "Sunday")
LOCATION_TYPES = ("single family", "condo", "apartment")
PURPOSES = ("Unknown", "ought", "able", "pri")


def arrow_type(kind: str) -> pa.DataType:
    if kind.startswith("decimal("):
        precision, scale = kind[8:-1].split(",")
        return pa.decimal128(int(precision), int(scale))
    return {I: pa.int32(), L: pa.int64(), S: pa.string(), D: pa.date32()}[kind]


def arrow_schema(table: str) -> pa.Schema:
    """Nullable but for the key: the writer makes the Delta schema of it."""
    return pa.schema([pa.field(name, arrow_type(kind), name not in NEVER_NULL)
                      for name, kind in SCHEMAS[table]])


def pool(column: str, whole: list, size: int) -> np.ndarray:
    """The first `size` of the column's domain, the constants the
    queries ask for in front; the whole domain where `size` takes it."""
    first = [v for v in FIRST.get(column, ()) if v in whole]
    rest = [v for v in whole if v not in first]
    return np.array((first + rest)[:max(size, len(first))])


class Columns:
    """The columns of one table in the making: values with the
    specification's share of nulls, as Arrow arrays of the declared type."""

    def __init__(self, table: str, rows: int, rng):
        self.schema = arrow_schema(table)
        self.rows, self.rng = rows, rng
        self.arrays = {}

    def nulls(self, name: str):
        if name in NEVER_NULL:
            return None
        return self.rng.random(self.rows) < NULL_SHARE

    def put(self, name: str, values, nulls="draw") -> None:
        """`nulls`: "draw" the specification's share (none in a key), a
        mask of the rows that are null, or None for none."""
        kind = self.schema.field(name).type
        mask = self.nulls(name) if isinstance(nulls, str) else nulls
        if pa.types.is_decimal(kind):
            self.arrays[name] = decimal_array(
                np.asarray(values, np.int64), kind, mask)
        else:
            self.arrays[name] = pa.array(np.asarray(values), kind, mask=mask)

    def coded(self, name: str, domain, codes, nulls="draw") -> None:
        """A column whose row `i` holds `domain[codes[i]]`: made from its
        few distinct values, however many the rows."""
        mask = self.nulls(name) if isinstance(nulls, str) else nulls
        kind = self.schema.field(name).type
        self.arrays[name] = pa.DictionaryArray.from_arrays(
            pa.array(np.asarray(codes, np.int32), mask=mask),
            pa.array(np.asarray(domain).tolist(), kind)).cast(kind)

    def choice(self, name: str, domain, nulls="draw") -> None:
        """A column drawn evenly from a small domain."""
        self.coded(name, domain,
                   self.rng.integers(0, len(domain), self.rows), nulls)

    def table(self) -> pa.Table:
        return pa.Table.from_arrays(
            [self.arrays[f.name] for f in self.schema], schema=self.schema)


def decimal_array(unscaled: np.ndarray, kind, mask) -> pa.Array:
    """`decimal128` from the unscaled integers: sixteen octets a value,
    the low word first, the high word the sign's."""
    words = np.empty((len(unscaled), 2), np.int64)
    words[:, 0] = unscaled
    words[:, 1] = unscaled >> 63
    return pa.Array.from_buffers(
        kind, len(unscaled), [validity(mask), pa.py_buffer(words)])


def validity(mask):
    if mask is None or not mask.any():
        return None
    return pa.py_buffer(np.packbits(~mask, bitorder="little"))


def ids(prefix: str, keys: np.ndarray) -> np.ndarray:
    """The sixteen characters of a business key: dsdgen's are sixteen
    letters; these are `AAAAAAAA` and the key in eight digits."""
    return np.char.add(prefix, np.char.zfill(keys.astype(str), 8))


def days(date_sk: np.ndarray) -> np.ndarray:
    """date32 (days since 1970-01-01) of `d_date_sk`."""
    return (date_sk - FIRST_DATE_SK + (FIRST_DATE - UNIX_EPOCH).days).astype(
        np.int32)


# ---- the nine dimensions ---------------------------------------------------

def date_dim(rows: int, rng) -> pa.Table:
    c = Columns("date_dim", rows, rng)
    sk = FIRST_DATE_SK + np.arange(rows)
    date = days(sk).astype("datetime64[D]")
    year = date.astype("datetime64[Y]").astype(int) + 1970
    month = date.astype("datetime64[M]").astype(int) % 12 + 1
    dom = (date - date.astype("datetime64[M]")).astype(int) + 1
    qoy = (month - 1) // 3 + 1
    dow = (days(sk) + 3) % 7            # 1970-01-01 was a Thursday; 0 Monday
    first_dom = sk - dom + 1
    c.put("d_date_sk", sk)
    c.put("d_date_id", ids("AAAAAAAA", sk), None)
    c.put("d_date", days(sk), None)
    c.put("d_month_seq", (year - 1900) * 12 + month - 1, None)
    c.put("d_week_seq", np.arange(rows) // 7 + 1, None)
    c.put("d_quarter_seq", (year - 1900) * 4 + qoy, None)
    c.put("d_year", year, None)
    c.put("d_dow", (dow + 1) % 7, None)     # the specification: 0 Sunday
    c.put("d_moy", month, None)
    c.put("d_dom", dom, None)
    c.put("d_qoy", qoy, None)
    c.put("d_fy_year", year, None)
    c.put("d_fy_quarter_seq", (year - 1900) * 4 + qoy, None)
    c.put("d_fy_week_seq", np.arange(rows) // 7 + 1, None)
    c.put("d_day_name", np.array(DAY_NAMES)[dow], None)
    c.put("d_quarter_name", np.char.add(np.char.add(year.astype(str), "Q"),
                                        qoy.astype(str)), None)
    c.put("d_holiday", np.where((month == 12) & (dom == 25), "Y", "N"), None)
    c.put("d_weekend", np.where(dow >= 5, "Y", "N"), None)
    c.put("d_following_holiday",
          np.where((month == 12) & (dom == 26), "Y", "N"), None)
    c.put("d_first_dom", first_dom, None)
    c.put("d_last_dom", first_dom + 27, None)
    c.put("d_same_day_ly", sk - 365, None)
    c.put("d_same_day_lq", sk - 91, None)
    for flag in ("day", "week", "month", "quarter", "year"):
        c.put(f"d_current_{flag}", np.full(rows, "N"), None)
    return c.table()


def time_dim(rows: int, rng) -> pa.Table:
    c = Columns("time_dim", rows, rng)
    t = np.arange(rows)
    hour = t // 3600
    c.put("t_time_sk", t)
    c.put("t_time_id", ids("AAAAAAAA", t), None)
    c.put("t_time", t, None)
    c.put("t_hour", hour, None)
    c.put("t_minute", t // 60 % 60, None)
    c.put("t_second", t % 60, None)
    c.put("t_am_pm", np.where(hour < 12, "AM", "PM"), None)
    c.put("t_shift", np.array(("third", "first", "second"))[hour // 8], None)
    c.put("t_sub_shift", np.array(("night", "morning", "afternoon",
                                   "evening"))[hour // 6], None)
    meal = np.where((hour >= 6) & (hour < 9), "breakfast", np.where(
        (hour >= 11) & (hour < 14), "lunch", np.where(
            (hour >= 17) & (hour < 20), "dinner", "")))
    c.put("t_meal_time", meal, meal == "")
    return c.table()


def item(rows: int, rng) -> pa.Table:
    c = Columns("item", rows, rng)
    sk = 1 + np.arange(rows)
    category = rng.integers(0, len(CATEGORIES), rows)
    klass = rng.integers(0, len(CLASSES), rows)
    brand = rng.integers(0, len(BRANDS), rows)
    number = rng.integers(1, 11, rows)
    # a thousandth of the items to a manufacturer, a hundredth to a manager
    manufact = pool("i_manufact_id", list(range(1, 1001)), rows // 18)
    manager = pool("i_manager_id", list(range(1, 101)), rows // 180)
    manufact_id = manufact[rng.integers(0, len(manufact), rows)]
    cost = rng.integers(100, 8_001, rows)
    c.put("i_item_sk", sk)
    # a business key has two versions on average (a history of the item)
    c.put("i_item_id", ids("AAAAAAAA", (sk + 1) // 2), None)
    c.put("i_rec_start_date", days(np.full(rows, FIRST_SOLD_SK - 300)))
    c.put("i_rec_end_date", days(np.full(rows, FIRST_SOLD_SK + 800)),
          sk % 2 == 0)
    c.put("i_item_desc", np.char.add("Item description ", sk.astype(str)))
    c.put("i_current_price", cost * rng.integers(110, 301, rows) // 100)
    c.put("i_wholesale_cost", cost)
    c.put("i_brand_id",
          (category + 1) * 1_000_000 + (klass + 1) * 1_000 + number)
    c.put("i_brand", np.char.add(np.char.add(
        np.array(BRANDS)[brand], np.array(BRANDS)[(brand + klass) % 10]),
        np.char.add(" #", number.astype(str))))
    c.put("i_class_id", klass + 1)
    c.put("i_class", np.array(CLASSES)[klass])
    c.put("i_category_id", category + 1)
    c.put("i_category", np.array(CATEGORIES)[category])
    c.put("i_manufact_id", manufact_id)
    c.put("i_manufact", np.array(SYLLABLES)[manufact_id % 9])
    c.choice("i_size", SIZES)
    c.put("i_formulation", np.char.add("formulation", sk.astype(str)))
    c.choice("i_color", COLORS)
    c.choice("i_units", UNITS)
    c.put("i_container", np.full(rows, "Unknown"))
    c.put("i_manager_id", manager[rng.integers(0, len(manager), rows)])
    c.put("i_product_name", np.char.add("product", sk.astype(str)))
    return c.table()


def customer(rows: int, rng, counts: dict) -> pa.Table:
    c = Columns("customer", rows, rng)
    sk = 1 + np.arange(rows)
    first_sale = FIRST_SOLD_SK - rng.integers(0, 3_650, rows)
    c.put("c_customer_sk", sk)
    c.put("c_customer_id", ids("AAAAAAAA", sk), None)
    c.put("c_current_cdemo_sk",
          rng.integers(1, counts["customer_demographics"] + 1, rows))
    c.put("c_current_hdemo_sk",
          rng.integers(1, counts["household_demographics"] + 1, rows))
    c.put("c_current_addr_sk",
          rng.integers(1, counts["customer_address"] + 1, rows))
    c.put("c_first_shipto_date_sk", first_sale + 30)
    c.put("c_first_sales_date_sk", first_sale)
    c.choice("c_salutation", SALUTATIONS)
    c.choice("c_first_name", FIRST_NAMES)
    c.choice("c_last_name", LAST_NAMES)
    c.choice("c_preferred_cust_flag", ("Y", "N"))
    c.put("c_birth_day", rng.integers(1, 29, rows))
    c.put("c_birth_month", rng.integers(1, 13, rows))
    c.put("c_birth_year", rng.integers(1924, 1993, rows))
    c.choice("c_birth_country", COUNTRIES)
    c.put("c_login", np.full(rows, ""), np.ones(rows, bool))
    c.put("c_email_address", np.char.add(np.char.add(
        "customer", sk.astype(str)), "@example.org"))
    c.put("c_last_review_date",
          (FIRST_SOLD_SK + rng.integers(0, 365, rows)).astype(str))
    return c.table()


def address_columns(c: Columns, prefix: str, rng) -> None:
    """What `customer_address` and `store` share: street to country."""
    c.put(f"{prefix}street_number", rng.integers(1, 1_000, c.rows).astype(str))
    c.choice(f"{prefix}street_name", STREETS)
    c.choice(f"{prefix}street_type", STREET_TYPES)
    c.put(f"{prefix}suite_number", np.char.add(
        "Suite ", rng.integers(0, 500, c.rows).astype(str)))
    c.choice(f"{prefix}county", COUNTIES)
    c.choice(f"{prefix}state", STATES)
    c.put(f"{prefix}zip", np.char.zfill(
        rng.integers(601, 100_000, c.rows).astype(str), 5))
    c.put(f"{prefix}country", np.full(c.rows, "United States"))
    c.put(f"{prefix}gmt_offset", -100 * rng.integers(5, 9, c.rows))


def customer_address(rows: int, rng) -> pa.Table:
    c = Columns("customer_address", rows, rng)
    sk = 1 + np.arange(rows)
    c.put("ca_address_sk", sk)
    c.put("ca_address_id", ids("AAAAAAAA", sk), None)
    address_columns(c, "ca_", rng)
    c.choice("ca_city", CITIES)
    c.choice("ca_location_type", LOCATION_TYPES)
    return c.table()


def cross_product(c: Columns, key: str, rows: int, parts) -> None:
    """A demographics table as the specification makes it: the key
    counts through the cross product of its attributes' domains, the
    first of `parts` turning fastest. No attribute is null."""
    at = np.arange(rows)
    c.put(key, at + 1)
    for name, domain in parts:
        c.coded(name, domain, at % len(domain), None)
        at = at // len(domain)


def customer_demographics(rows: int, rng) -> pa.Table:
    c = Columns("customer_demographics", rows, rng)
    cross_product(c, "cd_demo_sk", rows, (      # 2 x 5 x 7 x 20 x 4 x 7^3
        ("cd_gender", ("M", "F")), ("cd_marital_status", MARITAL),
        ("cd_education_status", EDUCATION),
        ("cd_purchase_estimate", np.arange(500, 10_001, 500)),
        ("cd_credit_rating", CREDIT), ("cd_dep_count", np.arange(7)),
        ("cd_dep_employed_count", np.arange(7)),
        ("cd_dep_college_count", np.arange(7))))
    return c.table()


def household_demographics(rows: int, rng) -> pa.Table:
    c = Columns("household_demographics", rows, rng)
    cross_product(c, "hd_demo_sk", rows, (      # 10 x 6 x 6 x 20
        ("hd_dep_count", np.arange(10)),
        ("hd_vehicle_count", np.arange(-1, 5)),
        ("hd_buy_potential", BUY_POTENTIAL),
        ("hd_income_band_sk", np.arange(1, 21))))
    return c.table()


def store(rows: int, rng) -> pa.Table:
    c = Columns("store", rows, rng)
    sk = 1 + np.arange(rows)
    names = pool("s_store_name", list(SYLLABLES), len(SYLLABLES))
    cities = pool("s_city", ["Midway", "Fairview", "Bethel", "Summit"], 4)
    c.put("s_store_sk", sk)
    c.put("s_store_id", ids("AAAAAAAA", (sk + 1) // 2), None)
    c.put("s_rec_start_date", days(np.full(rows, FIRST_SOLD_SK - 300)))
    c.put("s_rec_end_date", days(np.full(rows, FIRST_SOLD_SK + 800)),
          sk % 2 == 0)
    c.put("s_closed_date_sk", np.full(rows, 0), np.ones(rows, bool))
    # by the key and not drawn: a dozen stores hold every name and city
    c.put("s_store_name", names[(sk - 1) % len(names)], None)
    c.put("s_number_employees", rng.integers(200, 301, rows))
    c.put("s_floor_space", rng.integers(5_000_000, 10_000_001, rows))
    c.put("s_hours", np.full(rows, "8AM-8PM"))
    c.choice("s_manager", LAST_NAMES)
    c.put("s_market_id", rng.integers(1, 11, rows))
    c.put("s_geography_class", np.full(rows, "Unknown"))
    c.put("s_market_desc", np.char.add("Market of store ", sk.astype(str)))
    c.choice("s_market_manager", LAST_NAMES)
    c.put("s_division_id", np.ones(rows, int))
    c.put("s_division_name", np.full(rows, "Unknown"))
    c.put("s_company_id", np.ones(rows, int))
    c.put("s_company_name", np.full(rows, "Unknown"))
    address_columns(c, "s_", rng)
    c.put("s_city", cities[(sk - 1) % len(cities)], None)
    c.put("s_tax_precentage", rng.integers(0, 12, rows))
    return c.table()


def promotion(rows: int, rng, counts: dict) -> pa.Table:
    c = Columns("promotion", rows, rng)
    sk = 1 + np.arange(rows)
    start = FIRST_SOLD_SK + rng.integers(0, SOLD_DATES, rows)
    c.put("p_promo_sk", sk)
    c.put("p_promo_id", ids("AAAAAAAA", sk), None)
    c.put("p_start_date_sk", start)
    c.put("p_end_date_sk", start + rng.integers(1, 60, rows))
    c.put("p_item_sk", rng.integers(1, counts["item"] + 1, rows))
    c.put("p_cost", np.full(rows, 100_000))
    c.put("p_response_target", np.ones(rows, int))
    c.choice("p_promo_name", SYLLABLES)
    for channel in ("dmail", "email", "catalog", "tv", "radio", "press",
                    "event", "demo"):       # one promotion in ten uses it
        c.put(f"p_channel_{channel}",
              np.where(rng.random(rows) < 0.1, "Y", "N"))
    c.put("p_channel_details", np.char.add("Details of promotion ",
                                           sk.astype(str)))
    c.choice("p_purpose", PURPOSES)
    c.choice("p_discount_active", ("Y", "N"))
    return c.table()


# ---- the fact table --------------------------------------------------------

def store_sales(rows: int, rng, counts: dict, sold_date_step: int) -> pa.Table:
    """Tickets of 1..`TICKET_ITEMS` rows: a ticket is one customer's
    visit, so its date, time, customer, demographics, address, store and
    ticket number are one draw; item, promotion, quantity and the money
    are a row's. Priced as dsdgen prices, recalled (`assumed.pricing`),
    in cents."""
    c = Columns("store_sales", rows, rng)
    sizes = rng.integers(1, TICKET_ITEMS + 1, rows // (TICKET_ITEMS // 2) + 2)
    ticket = np.repeat(np.arange(len(sizes)), sizes)[:rows]
    assert len(ticket) == rows
    tickets = int(ticket[-1]) + 1

    def per_ticket(high: int, low: int = 1):
        return rng.integers(low, high + 1, tickets)[ticket]

    sold = np.arange(0, SOLD_DATES, sold_date_step)
    c.put("ss_sold_date_sk",
          FIRST_SOLD_SK + sold[rng.integers(0, len(sold), tickets)][ticket])
    c.put("ss_sold_time_sk", per_ticket(counts["time_dim"] - 1, 0))
    c.put("ss_item_sk", rng.integers(1, counts["item"] + 1, rows))
    c.put("ss_customer_sk", per_ticket(counts["customer"]))
    c.put("ss_cdemo_sk", per_ticket(counts["customer_demographics"]))
    c.put("ss_hdemo_sk", per_ticket(counts["household_demographics"]))
    c.put("ss_addr_sk", per_ticket(counts["customer_address"]))
    c.put("ss_store_sk", per_ticket(counts["store"]))
    c.put("ss_promo_sk", rng.integers(1, counts["promotion"] + 1, rows))
    c.put("ss_ticket_number", ticket + 1)
    qty = rng.integers(1, QUANTITY + 1, rows)
    c.put("ss_quantity", qty)

    cost = rng.integers(100, 10_001, rows)                  # 1.00 .. 100.00
    lst = cost * (100 + rng.integers(0, 201, rows)) // 100  # markup <= 200%
    sales = lst * (100 - rng.integers(0, 101, rows)) // 100
    ext_sales, ext_cost, ext_list = sales * qty, cost * qty, lst * qty
    coupon = np.where(rng.random(rows) < 0.2,
                      (ext_sales * rng.random(rows)).astype(np.int64), 0)
    net_paid = ext_sales - coupon
    tax = net_paid * rng.integers(0, 10, rows) // 100
    for name, cents in zip(MONEY, (
            cost, lst, sales, ext_list - ext_sales, ext_sales, ext_cost,
            ext_list, tax, coupon, net_paid, net_paid + tax,
            net_paid - ext_cost)):
        c.put(name, cents)
    return c.table()


def tables(params: dict, seed: int) -> dict:
    """The ten Arrow tables, by name, from the seed."""
    counts = dict(params["rows"])
    rngs = {name: np.random.default_rng([seed, i])
            for i, name in enumerate(SCHEMAS)}
    made = {
        "date_dim": date_dim(counts["date_dim"], rngs["date_dim"]),
        "time_dim": time_dim(counts["time_dim"], rngs["time_dim"]),
        "item": item(counts["item"], rngs["item"]),
        "customer": customer(counts["customer"], rngs["customer"], counts),
        "customer_address": customer_address(
            counts["customer_address"], rngs["customer_address"]),
        "customer_demographics": customer_demographics(
            counts["customer_demographics"], rngs["customer_demographics"]),
        "household_demographics": household_demographics(
            counts["household_demographics"],
            rngs["household_demographics"]),
        "store": store(counts["store"], rngs["store"]),
        "promotion": promotion(counts["promotion"], rngs["promotion"],
                               counts),
        "store_sales": store_sales(
            counts["store_sales"], rngs["store_sales"], counts,
            int(params.get("sold_date_step", 1))),
    }
    return {name: made[name] for name in SCHEMAS}


@dataclasses.dataclass
class Manifest:
    """What was loaded, for the harness's fixture line (the ten tables
    together) and for the driver: where each table lies, and the Arrow
    tables the reference answers from."""

    root: str
    table_paths: dict
    tables: dict
    rows: dict
    files: dict
    version: int
    load_actions: int
    log_bytes: int
    queries: list       # of the mix's fixture, for the driver

    def num_files(self) -> int:
        return sum(self.files.values())

    def release(self) -> None:
        """Let the Arrow tables go: the reference has loaded them."""
        self.tables = {}


def generate(root: str, params: dict, seed: int) -> Manifest:
    """Make the ten tables from `seed` and load each under `root` in one
    commit of the library's writer, on its default engine."""
    import delta_tpu.api as dta

    made = tables(params, seed)
    paths, files, actions, log_bytes = {}, {}, 0, 0
    for name, data in made.items():
        paths[name] = os.path.join(root, name)
        version = dta.write_table(paths[name], data, mode="error",
                                  partition_by=PARTITION_BY.get(name))
        assert version == 0, (name, version)
        for commit in glob.glob(os.path.join(paths[name], "_delta_log",
                                             "*.json")):
            log_bytes += os.path.getsize(commit)
            with open(commit, "rb") as f:
                lines = f.read().splitlines()
            actions += len(lines)
            files[name] = sum(line.startswith(b'{"add"') for line in lines)
    return Manifest(root, paths, made, {n: t.num_rows for n, t in made.items()},
                    files, 0, actions, log_bytes,
                    list(params.get("queries", ())))
