"""The controls of the comparison that decides ``correct``: each has to come
out NOT correct, beside a sound run that comes out correct.

``python3 -m hsbench.control --workload <cell> --seed <n> [--seconds s]`` runs
the cell as ``hsbench.run`` does (a short window is enough) and then, in the
same process,

- for a served cell puts the reference computed in float32, the nearest
  precision below the float64 the engine states, in the program's place;
- for a build cell breaks one stated guarantee at a time in a built index: a
  row dropped, two rows' payloads swapped, a row moved into another bucket.

Exit code 0 only if the sound run is correct and every control is not.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from hsbench import check


def float32_frames(frames: dict) -> dict:
    out = {}
    for name, f in frames.items():
        f = f.copy()
        for c in f.columns:
            if f[c].dtype.kind == "f":
                f[c] = f[c].astype(np.float32)
        out[name] = f
    return out


def float32_control(oracles, frames, requests, answers, limits) -> dict:
    """The oracle in float32 against the oracle in float64, over the same
    requests the window's answers were compared on."""
    low = float32_frames(frames)
    mismatches, gap = 0, 0.0
    for key, r in requests.items():
        got = oracles[r.template.name].answer(low, r.params)
        wrong, g = check.compare_answer(got, answers[key], r.template.ordered)
        mismatches += wrong
        gap = max(gap, g)
    numbers = {"answer.exact_mismatches": mismatches, "answer.float_rel_gap": gap}
    correct = all(numbers[k] <= limits[k] for k in numbers)
    return {"float32_aggregates": {"correct": correct, "numbers": numbers}}


def index_controls(files, index_dir, key, columns, num_buckets, facts, limits) -> dict:
    """Three faults, one at a time, in the files of a soundly built index."""
    sampled = sorted(facts["sums"])
    by_bucket = {}
    for f in files:
        by_bucket.setdefault(int(os.path.basename(f).split("-")[1]), []).append(f)
    a = by_bucket[sampled[0]][0]
    b = by_bucket[sampled[1]][0]
    keep = {p: p + ".sound" for p in (a, b)}
    for p, q in keep.items():
        shutil.copyfile(p, q)
    ta, tb = pq.read_table(a), pq.read_table(b)
    payload = [c for c in ta.column_names if c != key]
    first, last = ta.slice(0, 1), ta.slice(ta.num_rows - 1, 1)
    swapped = pa.concat_tables([
        pa.table({c: (last if c in payload else first).column(c) for c in ta.column_names}),
        ta.slice(1, ta.num_rows - 2),
        pa.table({c: (first if c in payload else last).column(c) for c in ta.column_names}),
    ])
    faults = {
        "row_dropped": {a: ta.slice(0, ta.num_rows - 1)},
        "payloads_swapped": {a: swapped},
        "row_in_wrong_bucket": {a: ta.slice(0, ta.num_rows - 1), b: pa.concat_tables([tb, last.select(tb.column_names)])},
    }
    out = {}
    try:
        for name, edits in faults.items():
            for path, table in edits.items():
                pq.write_table(table, path)
            numbers = check.index_numbers(files, index_dir, key, columns, num_buckets, facts)
            failed = sorted(k for k, v in numbers.items() if v > limits[k])
            out[name] = {"correct": not failed, "failed": failed}
            for p, q in keep.items():
                shutil.copyfile(q, p)
    finally:
        for p, q in keep.items():
            shutil.move(q, p)
    return out


def main(argv=None) -> int:
    from hsbench import run

    args = run.parse(argv)
    args.control = True
    result, code = run.execute(args)
    controls = result.pop("control", {})
    print("control: " + json.dumps({"seed": args.seed, "sound": result["correct"], **controls}), flush=True)
    held = bool(controls) and result["correct"] and not any(c["correct"] for c in controls.values())
    print("every control comes out not correct, as it must" if held
          else "CONTROL FAILED: a control passed the check, or the sound run did not", flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
