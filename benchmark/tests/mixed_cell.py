"""The mixed cell, handed to run.py without touching BENCHMARK.json:
`fixtures/mixed.json` (4 writers paced at 2 pushes/s beside 4 closed-loop
readers) under `allinone-k6` (no job inside a run) or
`fixtures/allinone-compacting.json` (upstream's 30 s cycle,
`compaction_in_run`). The tests drive it through `load_cell`; on the chip
the rehearsal runs it as

    python3 benchmark/tests/mixed_cell.py --seed <n> --seconds 40 --trace <0|1>
    python3 benchmark/tests/mixed_cell.py --config allinone-k6 --seed <n> ...

(every other argument is run.py's).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

import run  # noqa: E402

NAME = "allinone.mixed"
END_TO_END = ("ingest_spans_per_s", "queries_per_s", "query_p95_ms")
CONFIGS = {"allinone-k6": os.path.join(run.HERE, "configs", "allinone-k6.json"),
           "allinone-compacting": os.path.join(HERE, "fixtures", "allinone-compacting.json")}


def load_cell(config_name: str = "allinone-compacting"):
    """What run.load_cell gives for a cell of BENCHMARK.json, for this one:
    listed under the read and the write metrics at once, and under every
    per-layer metric of allinone.read and allinone.write."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {"name": NAME, "config": config_name, "traffic": "mixed", "chips": 1,
            "why": "4 paced writers + 4 readers in one window (a test fixture)"}
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if m["name"] in END_TO_END:
            m["workloads"].append(NAME)
    for m in bench["per_layer"]:
        if {"allinone.read", "allinone.write"} & set(m["workloads"]):
            m["workloads"].append(NAME)
    with open(CONFIGS[config_name]) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "fixtures", "mixed.json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIGS), default="allinone-compacting")
    args, rest = ap.parse_known_args()
    run.load_cell = lambda workload: load_cell(args.config)
    sys.exit(run.main(["--workload", NAME, *rest]))
