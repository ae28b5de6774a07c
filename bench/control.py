"""The control of the comparison that decides ``correct``: a whole run of
the cell, whose served finals are replaced, before the comparison, by the
plain reference's own answers computed in bfloat16, the precision below
the configuration's float32 (``run.control_finals``).  It has to come out
as not correct.

    python bench/control.py --workload <cell> --seed <n> --seconds <s>

It prints the run's result line, the numbers compared beside their
limits under ``checks``, and exits 0 when the control failed the
comparison as it should, 1 when it passed.  The benchmark's own runs never
run it; it needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH_DIR:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run as run_lib
    try:
        out = run_lib.run_cell(args.workload, args.seed, args.seconds, False,
                               control=True)
    except run_lib.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        run_lib.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 1 if out["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
