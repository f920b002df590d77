"""Paper-table reproductions: one function per table/figure of the paper.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV rows followed by CHECK lines that
assert the paper's claims against our implementation, and writes them as
``BENCH_paper_tables.json`` at the repo root.  The speed of the serving
system is measured on the chip by ``bench/``, not here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _emit(rows, checks, csv_lines, check_lines):
    for r in rows:
        name = r.get("name") or "/".join(
            str(r.get(k)) for k in ("bench", "model", "arch", "mode", "shape",
                                    "n", "w", "mesh") if r.get(k) is not None)
        us = r.get("us_per_call", 0)
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("bench", "name", "us_per_call"))
        csv_lines.append(f"{name},{us},{derived}")
    for claim, ok, detail in checks:
        check_lines.append(
            f"CHECK {'PASS' if ok else 'FAIL'}: {claim}"
            + (f" [{detail}]" if detail else ""))


def write_bench_json(group: str, rows, checks, out_dir: str) -> str:
    """Persist one benchmark group as BENCH_<group>.json (every row dict
    verbatim plus the CHECK verdicts)."""
    doc = {
        "bench": group,
        "rows": list(rows),
        "checks": [{"claim": c, "ok": bool(ok), "detail": d}
                   for c, ok, d in checks],
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{group}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=str)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-dir", default=REPO_ROOT,
                    help="where BENCH_paper_tables.json lands "
                         "(default: repo root)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_paper_tables.json")
    args = ap.parse_args()

    from benchmarks import paper_tables

    t0 = time.time()
    rows, checks = [], []
    for fn in (paper_tables.fig5, paper_tables.fig11, paper_tables.fig12,
               paper_tables.table1, paper_tables.table2,
               paper_tables.table3):
        r, c = fn()
        rows.extend(r)
        checks.extend(c)
    csv_lines = ["name,us_per_call,derived"]
    check_lines = []
    _emit(rows, checks, csv_lines, check_lines)
    print("\n".join(csv_lines))
    print()
    print("\n".join(check_lines))
    if not args.no_json:
        path = write_bench_json("paper_tables", rows, checks, args.json_dir)
        print(f"wrote {path}")
    n_fail = sum(1 for line in check_lines if "FAIL" in line)
    print(f"\n{len(check_lines) - n_fail}/{len(check_lines)} checks passed "
          f"({time.time() - t0:.1f}s)")
    if n_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
