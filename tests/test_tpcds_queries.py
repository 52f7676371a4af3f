"""Real TPC-DS v1.4 query texts through the SQL front-end.

The reference's gold standard runs the actual q1-q99 texts
(ref: goldstandard/PlanStabilitySuite.scala:83-290, query files under
src/test/resources/tpcds/queries). This suite parses those same texts with
the framework's SQL dialect, plans them onto the IR, checks

  - hyperspace-on results equal hyperspace-off results (checkAnswer), and
  - the normalized optimized-plan text against approved files
    (tests/approved_plans/tpcds_sql/, regen with HS_GENERATE_GOLDEN=1).

Tables use the complete 24-table schema (tests/tpcds_schema.py). Query texts
are read from the reference checkout; the whole module skips when it is not
available.
"""

import glob
import os
import re
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from tpcds_schema import TPCDS_SCHEMAS

QUERIES_DIR = "/root/reference/src/test/resources/tpcds/queries"
APPROVED_DIR = os.path.join(os.path.dirname(__file__), "approved_plans", "tpcds_sql")
GENERATE = os.environ.get("HS_GENERATE_GOLDEN", "") == "1"

pytestmark = pytest.mark.skipif(
    not os.path.isdir(QUERIES_DIR), reason="reference TPC-DS query texts not available"
)

# Round 2 grew window functions, GROUP BY ROLLUP/grouping(), and
# INTERSECT/EXCEPT; round 3 added expression join keys (q2/q8), OR-factored
# disjunctive join predicates (q13/q48), EXISTS decorrelation
# (q10/q16/q35/q69/q94), and correlated-scalar decorrelation
# (q1/q6/q30/q32/q41/q81/q92) — ALL 103 of the reference's query texts now
# plan, execute, and hold approved plans (the reference's own gold standard:
# goldstandard/PlanStabilitySuite.scala with 103 approved-plans entries).


def _all_query_names():
    files = glob.glob(os.path.join(QUERIES_DIR, "q*.sql"))
    return sorted(
        (os.path.basename(f)[:-4] for f in files),
        key=lambda s: (int(re.search(r"\d+", s).group()), s),
    )


EXPRESSIBLE = _all_query_names() if os.path.isdir(QUERIES_DIR) else []


def _query_text(qname):
    with open(os.path.join(QUERIES_DIR, f"{qname}.sql")) as f:
        return f.read()


# Wide vertical slices so the join/filter rules actually fire on the query
# texts (an index must cover every column its side contributes,
# ref: JoinIndexRule.scala:419-448); the dispatch goldens record which of
# the 103 rewrite and which physical path each takes
INDEXES = [
    ("store_sales", "ss_date", ["ss_sold_date_sk"],
     ["ss_item_sk", "ss_customer_sk", "ss_store_sk", "ss_cdemo_sk",
      "ss_hdemo_sk", "ss_addr_sk", "ss_promo_sk", "ss_ticket_number",
      "ss_quantity", "ss_sales_price", "ss_ext_sales_price",
      "ss_ext_discount_amt", "ss_wholesale_cost", "ss_list_price",
      "ss_ext_list_price", "ss_ext_wholesale_cost", "ss_coupon_amt",
      "ss_ext_tax", "ss_net_paid", "ss_net_paid_inc_tax", "ss_net_profit"]),
    ("store_sales", "ss_item", ["ss_item_sk"],
     ["ss_sold_date_sk", "ss_customer_sk", "ss_store_sk", "ss_ticket_number",
      "ss_quantity", "ss_sales_price", "ss_ext_sales_price", "ss_net_profit",
      "ss_net_paid", "ss_wholesale_cost"]),
    ("store_sales", "ss_customer", ["ss_customer_sk"],
     ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_ticket_number",
      "ss_quantity", "ss_sales_price", "ss_ext_sales_price", "ss_net_profit"]),
    ("catalog_sales", "cs_date", ["cs_sold_date_sk"],
     ["cs_item_sk", "cs_bill_customer_sk", "cs_ship_customer_sk",
      "cs_order_number", "cs_quantity", "cs_list_price", "cs_sales_price",
      "cs_ext_sales_price", "cs_ext_discount_amt", "cs_ext_list_price",
      "cs_wholesale_cost", "cs_coupon_amt", "cs_net_profit", "cs_net_paid",
      "cs_warehouse_sk", "cs_promo_sk", "cs_call_center_sk",
      "cs_ship_mode_sk", "cs_ship_date_sk", "cs_ship_addr_sk",
      "cs_bill_cdemo_sk", "cs_bill_hdemo_sk"]),
    ("web_sales", "ws_date", ["ws_sold_date_sk"],
     ["ws_item_sk", "ws_bill_customer_sk", "ws_ship_customer_sk",
      "ws_order_number", "ws_quantity", "ws_list_price", "ws_sales_price",
      "ws_ext_sales_price", "ws_ext_discount_amt", "ws_ext_list_price",
      "ws_wholesale_cost", "ws_net_profit", "ws_net_paid",
      "ws_warehouse_sk", "ws_promo_sk", "ws_web_site_sk", "ws_web_page_sk",
      "ws_ship_addr_sk", "ws_bill_addr_sk"]),
    ("item", "i_sk", ["i_item_sk"],
     ["i_item_id", "i_item_desc", "i_brand_id", "i_brand", "i_class_id",
      "i_class", "i_category_id", "i_category", "i_manufact_id",
      "i_manufact", "i_current_price", "i_color", "i_units", "i_size",
      "i_manager_id", "i_product_name"]),
    ("date_dim", "d_sk", ["d_date_sk"],
     ["d_date", "d_year", "d_moy", "d_dom", "d_qoy", "d_dow", "d_month_seq",
      "d_week_seq", "d_quarter_name", "d_day_name", "d_date_id"]),
    ("customer", "c_sk", ["c_customer_sk"],
     ["c_customer_id", "c_first_name", "c_last_name", "c_salutation",
      "c_preferred_cust_flag", "c_current_addr_sk", "c_current_cdemo_sk",
      "c_current_hdemo_sk", "c_birth_country", "c_birth_year",
      "c_birth_month", "c_birth_day", "c_first_sales_date_sk",
      "c_first_shipto_date_sk", "c_email_address", "c_login"]),
]


def _wide(table, keyed):
    """Every non-key column as an included column — the covering-index
    shape the reference's own suites build for star joins (an index must
    cover every column its side contributes, JoinIndexRule.scala:419-448)."""
    return [c for c in TPCDS_SCHEMAS[table] if c not in keyed]


# Round-5 leverage expansion, driven by a whyNot sweep over the 103 texts
# (the CandidateIndexAnalyzer.scala:29-346 workflow): every fact-table FK used as a join key gets a bucketed slice,
# the returns tables join their sales counterparts on composite
# (item, ticket/order) keys, and every dimension is covered on its
# surrogate key.
_KEYED = [
    # store_sales FK slices + the returns composite
    ("store_sales", "ss_item_ticket", ["ss_item_sk", "ss_ticket_number"]),
    ("store_sales", "ss_cdemo", ["ss_cdemo_sk"]),
    ("store_sales", "ss_hdemo", ["ss_hdemo_sk"]),
    ("store_sales", "ss_addr", ["ss_addr_sk"]),
    ("store_sales", "ss_store", ["ss_store_sk"]),
    ("store_sales", "ss_promo", ["ss_promo_sk"]),
    # catalog_sales
    ("catalog_sales", "cs_item", ["cs_item_sk"]),
    ("catalog_sales", "cs_customer", ["cs_bill_customer_sk"]),
    ("catalog_sales", "cs_item_order", ["cs_item_sk", "cs_order_number"]),
    # web_sales
    ("web_sales", "ws_item", ["ws_item_sk"]),
    ("web_sales", "ws_customer", ["ws_bill_customer_sk"]),
    ("web_sales", "ws_item_order", ["ws_item_sk", "ws_order_number"]),
    ("web_sales", "ws_order", ["ws_order_number"]),
    # returns tables
    ("store_returns", "sr_date", ["sr_returned_date_sk"]),
    ("store_returns", "sr_item_ticket", ["sr_item_sk", "sr_ticket_number"]),
    ("store_returns", "sr_item", ["sr_item_sk"]),
    ("store_returns", "sr_customer", ["sr_customer_sk"]),
    ("catalog_returns", "cr_date", ["cr_returned_date_sk"]),
    ("catalog_returns", "cr_item_order", ["cr_item_sk", "cr_order_number"]),
    ("catalog_returns", "cr_item", ["cr_item_sk"]),
    ("web_returns", "wr_date", ["wr_returned_date_sk"]),
    ("web_returns", "wr_item_order", ["wr_item_sk", "wr_order_number"]),
    ("web_returns", "wr_order", ["wr_order_number"]),
    # inventory
    ("inventory", "inv_date", ["inv_date_sk"]),
    ("inventory", "inv_item", ["inv_item_sk"]),
    # dimensions on their surrogate keys
    ("customer_address", "ca_sk", ["ca_address_sk"]),
    ("customer_demographics", "cd_sk", ["cd_demo_sk"]),
    ("household_demographics", "hd_sk", ["hd_demo_sk"]),
    ("store", "s_sk", ["s_store_sk"]),
    ("promotion", "p_sk", ["p_promo_sk"]),
    ("warehouse", "w_sk", ["w_warehouse_sk"]),
    ("time_dim", "t_sk", ["t_time_sk"]),
    ("ship_mode", "sm_sk", ["sm_ship_mode_sk"]),
    ("reason", "r_sk", ["r_reason_sk"]),
    ("income_band", "ib_sk", ["ib_income_band_sk"]),
    ("web_site", "web_sk", ["web_site_sk"]),
    ("web_page", "wp_sk", ["wp_web_page_sk"]),
    ("call_center", "cc_sk", ["cc_call_center_sk"]),
    ("catalog_page", "cp_sk", ["cp_catalog_page_sk"]),
    # second sweep iteration: the 26 remaining non-rewriters' actual join
    # keys (3-col store/returns composites q17/q25/q29/q50, the
    # sr<->cs customer+item bridge, ship/warehouse/time FKs q62/q66/q99,
    # customer-side current_*_sk chains q84/q85, cs demographics q18/q26)
    ("store_sales", "ss_cust_item_ticket",
     ["ss_customer_sk", "ss_item_sk", "ss_ticket_number"]),
    ("store_sales", "ss_time", ["ss_sold_time_sk"]),
    ("store_returns", "sr_cust_item_ticket",
     ["sr_customer_sk", "sr_item_sk", "sr_ticket_number"]),
    ("store_returns", "sr_cust_item", ["sr_customer_sk", "sr_item_sk"]),
    ("store_returns", "sr_cdemo", ["sr_cdemo_sk"]),
    ("store_returns", "sr_reason", ["sr_reason_sk"]),
    ("catalog_sales", "cs_cdemo", ["cs_bill_cdemo_sk"]),
    ("catalog_sales", "cs_cust_item", ["cs_bill_customer_sk", "cs_item_sk"]),
    ("catalog_sales", "cs_warehouse", ["cs_warehouse_sk"]),
    ("catalog_sales", "cs_shipmode", ["cs_ship_mode_sk"]),
    ("catalog_sales", "cs_time", ["cs_sold_time_sk"]),
    ("catalog_sales", "cs_shipdate", ["cs_ship_date_sk"]),
    ("catalog_sales", "cs_callcenter", ["cs_call_center_sk"]),
    ("web_sales", "ws_warehouse", ["ws_warehouse_sk"]),
    ("web_sales", "ws_shipmode", ["ws_ship_mode_sk"]),
    ("web_sales", "ws_website", ["ws_web_site_sk"]),
    ("web_sales", "ws_shipdate", ["ws_ship_date_sk"]),
    ("web_sales", "ws_time", ["ws_sold_time_sk"]),
    ("web_sales", "ws_shipaddr", ["ws_ship_addr_sk"]),
    ("web_sales", "ws_webpage", ["ws_web_page_sk"]),
    ("inventory", "inv_wh", ["inv_warehouse_sk"]),
    ("customer", "c_addr", ["c_current_addr_sk"]),
    ("customer", "c_cdemo", ["c_current_cdemo_sk"]),
    ("customer", "c_hdemo", ["c_current_hdemo_sk"]),
    ("household_demographics", "hd_ib", ["hd_income_band_sk"]),
    # third iteration: q90 (ws ship-demographics/time/page legs) and q91
    # (cr call-center + returning-customer legs)
    ("web_sales", "ws_shiphdemo", ["ws_ship_hdemo_sk"]),
    ("catalog_returns", "cr_callcenter", ["cr_call_center_sk"]),
    ("catalog_returns", "cr_ret_customer", ["cr_returning_customer_sk"]),
]
INDEXES = INDEXES + [(t, n, k, _wide(t, k)) for t, n, k in _KEYED]


# Queries whose predicate conjunctions the small shaped fixture cannot
# populate (multi-channel revenue-band/self-intersection shapes); tracked so
# they can only shrink. Everything else MUST return rows — an empty result
# makes the on/off parity check vacuous.
EMPTY_OK = {
    "q14b", "q23b", "q24b", "q31", "q39b", "q54", "q58", "q60", "q64",
    "q72", "q83", "q85", "q91",
}


@pytest.fixture(scope="module")
def tpcds(tmp_path_factory):
    from tpcds_data import arrow_tables

    root = str(tmp_path_factory.mktemp("tpcds_sql"))
    sysp = os.path.join(root, "_indexes")
    os.makedirs(sysp)
    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: sysp, hst.keys.NUM_BUCKETS: 4})
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    for name, table in arrow_tables().items():
        d = os.path.join(root, name)
        os.makedirs(d)
        pq.write_table(table, os.path.join(d, "part-00000.parquet"))
        sess.read_parquet(d).create_or_replace_temp_view(name)
    for table, idx_name, indexed, included in INDEXES:
        hs.create_index(
            sess._temp_views[table], hst.CoveringIndexConfig(idx_name, indexed, included)
        )
    sess.enable_hyperspace()
    yield sess, root
    hst.set_session(None)


def _normalize(text, root):
    return text.replace(root, "<TPCDS>")


def _norm_key(v):
    # one totally-ordered domain: NaN == NaN, NULLs sortable, every value
    # stringified (a rollup NULL-filled column mixes types); floats at LOW
    # precision so summation-order noise cannot split sort keys
    if v is None:
        return "\x00NULL"
    if isinstance(v, float):
        if v != v:
            return "NaN"
        return f"{v:.3g}"
    return str(v)


def _sorted_rows(batch):
    cols = sorted(batch.keys())
    if not cols:
        return []
    rows = list(zip(*[batch[k].tolist() for k in cols]))
    return sorted(rows, key=lambda r: tuple(_norm_key(v) for v in r))


def _rows_close(a, b):
    import math

    for x, y in zip(a, b):
        if isinstance(x, float) and isinstance(y, float):
            if x != x and y != y:
                continue
            if not math.isclose(x, y, rel_tol=1e-6, abs_tol=1e-6):
                return False
        elif _norm_key(x) != _norm_key(y):
            return False
    return True


def _assert_rows_equal(on, off, qname):
    """Row-set equality with relative float tolerance: a bucketed (index)
    scan sums in a different order than a file scan, and float addition is
    not associative — string rounding alone straddles digit boundaries.
    Rows sort on LOW-precision keys, so rows tying at key precision are
    matched as a multiset (greedy) rather than pairwise — tie order is not
    deterministic across the two runs."""
    from itertools import groupby

    ron, roff = _sorted_rows(on), _sorted_rows(off)
    assert len(ron) == len(roff), f"{qname}: row count differs with hyperspace on vs off"

    def key(r):
        return tuple(_norm_key(v) for v in r)

    ga = {k: list(g) for k, g in groupby(ron, key)}
    gb = {k: list(g) for k, g in groupby(roff, key)}
    assert sorted(ga) == sorted(gb), f"{qname}: row keys differ with hyperspace on vs off"
    for k, rows_a in ga.items():
        rows_b = list(gb[k])
        assert len(rows_a) == len(rows_b), f"{qname}: tie-group size differs at {k}"
        for a in rows_a:
            hit = next((i for i, b in enumerate(rows_b) if _rows_close(a, b)), None)
            assert hit is not None, (
                f"{qname}: row {a} has no tolerant match with hyperspace on vs off"
            )
            rows_b.pop(hit)


@pytest.mark.parametrize("qname", EXPRESSIBLE)
def test_query_plans_and_answers(tpcds, qname):
    sess, root = tpcds
    q = sess.sql(_query_text(qname))

    plan_text = _normalize(q.optimized_plan().pretty(), root)
    path = os.path.join(APPROVED_DIR, f"{qname}.txt")
    if GENERATE:
        os.makedirs(APPROVED_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(plan_text)
    else:
        with open(path) as f:
            assert plan_text == f.read(), (
                f"plan for {qname} changed; review and regen with HS_GENERATE_GOLDEN=1"
            )

    on = q.collect()
    sess.disable_hyperspace()
    try:
        off = q.collect()
    finally:
        sess.enable_hyperspace()
    assert sorted(on.keys()) == sorted(off.keys()), qname
    _assert_rows_equal(on, off, qname)
    # the shaped fixture (tpcds_data.py) makes parity non-vacuous: outside
    # the EMPTY_OK allowlist a query MUST produce rows, and an allowlisted
    # query that starts producing rows must be removed (ratchet both ways)
    n_rows = len(next(iter(on.values()))) if on else 0
    if qname in EMPTY_OK:
        assert n_rows == 0, f"{qname} now returns rows; remove it from EMPTY_OK"
    else:
        assert n_rows > 0, f"{qname} returned no rows; fixture degraded"

    # physical-dispatch golden (ref: PlanStabilitySuite approves the
    # *executedPlan*, scala:83-290) — see test_tpch_queries.py
    from hyperspace_tpu.exec import device as D
    from hyperspace_tpu.exec import io as hs_io
    from hyperspace_tpu.exec import trace

    hs_io.clear_io_cache()
    D.clear_device_cache()
    sess.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
    try:
        with trace.recording() as events:
            q.collect()
    finally:
        sess.conf.unset(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS)
    dispatch = trace.summarize(events)
    dpath = os.path.join(APPROVED_DIR, f"{qname}.dispatch.txt")
    if GENERATE:
        with open(dpath, "w") as f:
            f.write(dispatch)
    else:
        with open(dpath) as f:
            assert dispatch == f.read(), (
                f"physical dispatch for {qname} changed; review and regen "
                "with HS_GENERATE_GOLDEN=1"
            )


def test_full_gold_standard_parity():
    """The ratchet: every one of the reference's 103 query texts is
    expressible and has an approved plan."""
    if os.path.isdir(QUERIES_DIR):
        assert len(EXPRESSIBLE) == 103
        missing = [
            q
            for q in EXPRESSIBLE
            if not os.path.exists(os.path.join(APPROVED_DIR, f"{q}.txt"))
        ]
        assert not missing, f"queries without approved plans: {missing}"
