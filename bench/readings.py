#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``), many
seeds in one process on the chip: sound runs of the program, the control
(the reference one precision lower in the program's place) and, on
chosen seeds, the witness replays of ``check.witness_readings``.

    python bench/readings.py --workload bert-learn-s64 --seconds 2 \\
        --seeds 101,102 --control-seeds 201 --witness-seeds 101 \\
        --out readings.jsonl

Each run appends one JSON line to ``--out``: the seed, the kind of run,
every compared number and every reading.  The benchmark's own runs
(``bench/run.py``) never run the control or the witness.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    c = run.load_cell(a.workload)
    witness = set(_seeds(a.witness_seeds))
    plan = ([(s, False) for s in _seeds(a.seeds)]
            + [(s, True) for s in _seeds(a.control_seeds)])
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    for seed, control in plan:
        keep = {}
        out = run.run_cell(c, seed, a.seconds, False, control=control,
                           keep=keep)
        line = {"seed": seed, "kind": "control" if control else "program",
                "correct": out["correct"], "checks": out["checks"],
                "readings": out["info"]["readings"],
                "metrics": out["metrics"]}
        if seed in witness and not control:
            import check
            ev = keep["evidence"]
            line["witness"] = check.witness_readings(
                c["cfg"], c["mix"], seed, keep["weights"], ev["docs_by_tick"],
                ev["outs_by_tick"], ev["calls"])
        keep.clear()
        gc.collect()
        with open(a.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
