"""The five cells are dealt the requests the harness dealt them before roles
came (PR 37): for each traffic file, on two seeds, a digest of the first 200
`(method, path, sha256(body), expect)` of every client, over a small store
whose IDs the finds draw from. `golden_requests.json` was computed with the
parent's `traffic.py` (commit 4157ae3) by this file's `digest`, before any
edit:

    python benchmark/tests/test_golden_requests.py > benchmark/tests/golden_requests.json
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

from conftest import BENCH, HERE, ROOT

import corpus
import run
import traffic as tr

SEEDS = (7, 3000000019)  # the second beyond 32 signed bits, as the driver's are
BASE_S = 1_700_000_040  # a step boundary, as run.py's base_s is
REQUESTS = 200
FILES = ("mesh", "read", "repeat", "search", "write")


def cell_of(traffic_name: str) -> tuple:
    """(configuration, traffic) of the cell BENCHMARK.json runs the mix in."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["traffic"] == traffic_name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic_name + ".json")) as f:
        return config, json.load(f)


def digest(traffic_name: str, seed: int) -> str:
    config, traffic = cell_of(traffic_name)
    multitenant = bool(config["server"].get("multitenancy_enabled"))
    tenants = run.tenants_of(config)
    data = {"tenants": tenants, "blocks_per_tenant": config["blocks_per_tenant"],
            "traces_per_block": 32, "spans_per_trace": config["spans_per_trace"],
            "resend_fraction": config["resend_fraction"]}
    store = corpus.make_store(seed, data, BASE_S)
    pool = []
    if "pool_bodies" in traffic:
        traffic["pool_bodies"] = 4
        pool = tr.make_pool(traffic, seed, data["spans_per_trace"], BASE_S)
    src = tr.Source(traffic, tenants, multitenant, BASE_S,
                    {t: np.array([h for b in bl for h in corpus.trace_hex(b)], dtype=object)
                     for t, bl in store.items()}, pool)
    h = hashlib.sha256()
    for n in range(traffic["clients"]):
        client = tr.Client(n, seed, src, 0, [])
        for _ in range(REQUESTS):
            r = client.next_request()
            body = hashlib.sha256(r.body).hexdigest() if r.body is not None else "-"
            h.update(f"{n} {r.method} {r.path} {body} {r.expect}\n".encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic_name", FILES)
def test_a_file_without_roles_deals_what_it_dealt_before(traffic_name, seed):
    with open(os.path.join(HERE, "golden_requests.json")) as f:
        golden = json.load(f)
    assert digest(traffic_name, seed) == golden[traffic_name][str(seed)]


if __name__ == "__main__":
    json.dump({name: {str(s): digest(name, s) for s in SEEDS} for name in FILES},
              sys.stdout, indent=1)
    print()
