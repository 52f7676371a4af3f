"""Quick start: create a covering index and watch queries use it.

Mirrors the reference's examples/ walkthrough (Hyperspace quick-start docs):
generate a small dataset, index it, run filter/join/aggregate queries with
the optimizer on, and inspect explain/whyNot output.

    python examples/quickstart.py
"""

import os
import sys
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import hyperspace_tpu as hst


def main() -> None:
    root = tempfile.mkdtemp(prefix="hs_quickstart_")
    data = os.path.join(root, "employees")
    os.makedirs(data)
    rng = np.random.default_rng(0)
    n = 200_000
    pq.write_table(
        pa.table(
            {
                "emp_id": np.arange(n, dtype=np.int64),
                "dept_id": rng.integers(0, 50, n).astype(np.int64),
                "salary": np.round(rng.uniform(40_000, 200_000, n), 2),
            }
        ),
        os.path.join(data, "part-0.parquet"),
    )
    depts = os.path.join(root, "departments")
    os.makedirs(depts)
    pq.write_table(
        pa.table(
            {
                "dept_id": np.arange(50, dtype=np.int64),
                "dept_name": np.array([f"dept_{i}" for i in range(50)]),
            }
        ),
        os.path.join(depts, "part-0.parquet"),
    )

    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: os.path.join(root, "indexes"),
            hst.keys.NUM_BUCKETS: 16,
            hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True,
        }
    )
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)

    emp = sess.read_parquet(data)
    dept = sess.read_parquet(depts)

    print("== create indexes ==")
    hs.create_index(emp, hst.CoveringIndexConfig("emp_dept", ["dept_id"], ["salary", "emp_id"]))
    hs.create_index(dept, hst.CoveringIndexConfig("dept_pk", ["dept_id"], ["dept_name"]))
    print(hs.indexes(), "\n")

    sess.enable_hyperspace()

    print("== filter query (bucket-pruned index scan) ==")
    q = emp.filter(hst.col("dept_id") == 7).select("emp_id", "salary")
    print(hs.explain(q), "\n")

    print("== shuffle-free indexed join + aggregation ==")
    top = (
        emp.join(dept, on=["dept_id"])
        .group_by("dept_name")
        .agg(headcount=("*", "count"), payroll=("salary", "sum"))
        .order_by("payroll", ascending=False)
        .limit(5)
    )
    for row in top.to_pandas().itertuples(index=False):
        print(f"  {row.dept_name:>10}  headcount={row.headcount:>5}  payroll={row.payroll:>14,.2f}")
    print()

    print("== whyNot: why an index was not used ==")
    q2 = emp.filter(hst.col("salary") > 150_000).select("emp_id")
    print(hs.why_not(q2))


if __name__ == "__main__":
    main()
