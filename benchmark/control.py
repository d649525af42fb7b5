#!/usr/bin/env python3
"""The controls, at a cell's own size: the reference with one stated
guarantee broken, put in the program's place. Each has to come out as
NOT correct, or the comparison in check.py proves nothing.

    python benchmark/control.py --workload <cell> --seed <n> [--requests 400]
                                [--compaction-in-run]

Draws the cell's store and as many requests of its mix as a run makes,
answers them with each control that the cell's operations call for,
compares with the true reference and prints every number beside its
limit. Exit 0 when every control failed the comparison (as it must), 1
when one passed. The read cells' controls are numpy only: no server, no
JAX, no chip.

  cells that ask rate()       Bf16Counts: span counts summed as the MXU
                              sums run lengths at default precision
                              (the fault PR 22 found on the chip)
  cells that ask quantiles    CoarseQuantiles: a sketch of 4 sub-buckets
                              an octave where the configuration says 8
  multi-tenant cells          LeakyTenants: searches see every tenant
  a configuration that says   HalfCombined: a merge that combines the
  compaction_in_run, or       copies of every other re-sent trace, which
  --compaction-in-run         no state of the store gives; every control
                              is then held against the comparison that
                              accepts any partition's answer
  write cells                 the program behind a connection that
                              acknowledges every 20th push and loses it
                              (this one starts the server: it needs the
                              chip, or --dry-traces for the rehearsal)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import numpy as np  # noqa: E402

import check  # noqa: E402
import corpus  # noqa: E402
import run  # noqa: E402
import traffic as tr  # noqa: E402
from reference import (Bf16Counts, CoarseQuantiles, HalfCombined, LeakyTenants,  # noqa: E402
                       Reference)


def lost_pushes(workload: str, seed: int, dry_traces: int) -> dict:
    """Write cells have no reference to stand in: the control is the
    program itself behind a connection that answers every 20th push with
    200 and never delivers it. Needs the chip (or --dry-traces: the CPU
    rehearsal); one short window at the cell's own load."""
    import contextlib
    import io

    real = tr.Client.exchange

    def lossy(self, req, headers):
        if req.op == "push" and self._sent % 20 == 0:
            return 200, b""
        return real(self, req, headers)

    tr.Client.exchange = lossy
    out = io.StringIO()
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "12", "--trace", "0"]
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv + (["--cpu-dry-run", "--dry-traces", str(dry_traces)]
                                  if dry_traces else []))
    finally:
        tr.Client.exchange = real
    if rc != 0:
        raise SystemExit(f"the run behind the lossy connection failed:\n{out.getvalue()[-2000:]}")
    doc = json.loads(out.getvalue().strip().splitlines()[-1])
    return {**{k: v["value"] for k, v in doc["compared"].items()},
            "_compared_items": doc["attempted"]}


RATES = {"rate_by_name", "rate_total", "rate_by_service"}


def controls_of(workload: str, seed: int, n_requests: int, dry_traces: int = 0,
                compaction_in_run: bool = False) -> dict:
    """{control's name: its numbers under check.compare} for the cell."""
    _, _, config, traffic = run.load_cell(workload)
    multitenant = bool(config["server"].get("multitenancy_enabled"))
    tenants = run.tenants_of(config)
    data = run.store_data(config, tenants, dry_traces)
    compaction_in_run = compaction_in_run or bool(config.get("compaction_in_run"))
    ops = tr.ops_of(traffic)
    out = {"lost pushes": lost_pushes(workload, seed, dry_traces)} if "push" in ops else {}
    readers = [role for role in tr.roles_of(traffic)
               if not any(e["op"] == "push" for e in role["deck"])]
    if not readers:
        return out
    store = corpus.make_store(seed, data, 1_700_000_000)
    src = tr.Source(traffic, tenants, multitenant, 1_700_000_000,
                    {t: np.array([h for b in bl for h in corpus.trace_hex(b)], dtype=object)
                     for t, bl in store.items()})
    truth = {t: Reference(bl, compaction_in_run) for t, bl in store.items()}
    controls = {}
    if ops & RATES:
        controls["Bf16Counts"] = {t: Bf16Counts(bl) for t, bl in store.items()}
        if compaction_in_run:
            controls["HalfCombined"] = {t: HalfCombined(bl) for t, bl in store.items()}
    if "quantiles" in ops:
        controls["CoarseQuantiles"] = {t: CoarseQuantiles(bl) for t, bl in store.items()}
    if multitenant:
        controls["LeakyTenants"] = {
            t: LeakyTenants(bl, [b for o, obl in store.items() if o != t for b in obl])
            for t, bl in store.items()}
    if not controls:
        raise SystemExit(f"no control is defined for the operations of {workload}")
    requests = []
    clients = tr.client_roles(readers)
    for c, role in enumerate(clients):
        client = tr.Client(c, seed, src, 0, [], role=role)
        requests += [(c, client.next_request()) for _ in range(n_requests // len(clients))]
    for name, control in controls.items():
        records = []
        for c, req in requests:
            ref = control[req.tenant]
            if req.op == "find":  # none of this run was acknowledged: every find is of the store
                answer = ref.find(*req.args)
            elif req.op == "quantiles":
                answer = {q: [lo] for q, (lo, _) in ref.quantiles(*req.args, tr.QUANTILES).items()}
            else:
                answer = getattr(ref, req.op)(*req.args)
            records.append(tr.Record(req, c, 0.0, 0.0, req.expect, answer))
        out[name] = check.compare(records, truth)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--dry-traces", type=int, default=0, help="a small store, for a test")
    ap.add_argument("--compaction-in-run", action="store_true",
                    help="hold the controls against the comparison of a configuration "
                         "that says compaction_in_run, and add HalfCombined")
    args = ap.parse_args(argv)
    passed = []
    for control, numbers in controls_of(args.workload, args.seed, args.requests,
                                        args.dry_traces, args.compaction_in_run).items():
        correct, compared = check.verdict(numbers)
        head = f"[control] {args.workload} seed {args.seed} {control}"
        for name, e in compared.items():
            print(f"{head} {name}: {e['value']} (limit {e['limit']})")
        print(f"{head}: compared {numbers['_compared_items']} items, correct={correct} "
              "(has to be False)")
        passed += [control] if correct else []
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
