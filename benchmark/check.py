"""What decides `correct`: every answer the window produced, compared
with the plain reference once the window has closed. Each number has a
limit of its own; the readings the limits were set from are in
../PERF.md. An exact comparison has the limit 0; the quantiles' limit is
the one the configuration states (the sketch's documented error).
"""

from __future__ import annotations

QUANTILE_REL_ERR = 0.125  # metrics_engine/plan.py: 8 sub-buckets an octave

LIMITS = {
    "unanswered": 0,        # requests with no answer or another status than expected
    "find_wrong": 0,        # find-by-ID answers whose span-id set differs
    "search_wrong": 0,      # tag searches whose hit set differs
    "traceql_wrong": 0,     # TraceQL filters whose hit set differs
    "count_wrong": 0,       # query_range answers with a series count that differs
    "quantile_rel_err": QUANTILE_REL_ERR,  # worst relative error of a quantile
    "readback_wrong": 0,    # acknowledged traces read back with other spans
    "span_count_gap": 0,    # acknowledged spans the store does not count
    "server_exit": 0,       # the server's exit code after POST /shutdown
    "log_errors": 0,        # ERROR or CRITICAL lines in the server's log over the whole run
}

_NUMBER_OF = {"find": "find_wrong", "search_tags": "search_wrong",
              "traceql_filter": "traceql_wrong", "rate_by_name": "count_wrong",
              "rate_total": "count_wrong", "rate_by_service": "count_wrong",
              "quantiles": "quantile_rel_err"}


def _counts_equal(got: dict, want: dict) -> bool:
    return (set(got) == set(want)
            and all(abs(got[k] - want[k]) < 1e-3 for k in want))


def _wrongness(r, ref) -> tuple:
    """(how wrong the answer is, how many items were compared)."""
    op, args = r.req.op, r.req.args
    if op == "quantiles":
        from traffic import QUANTILES

        want = ref.quantiles(*args, QUANTILES)
        # one non-empty step holds every span: anything else is an error of 100 %
        errs = [min(abs(got[0] - t) / t for t in true) if len(got) == 1 else 1.0
                for true, got in ((true, r.answer.get(q, [])) for q, true in want.items())]
        return max(errs), len(want)
    want = getattr(ref, op)(*args)  # find: None for an absent id, as a 404 parses
    if isinstance(want, dict):
        return not _counts_equal(r.answer, want), len(want)
    return r.answer != want, len(want or ())


def compare(records: list, refs: dict) -> dict:
    """{number: value} over the answered requests of the window. `refs`
    maps a tenant to its Reference (or to a control in its place)."""
    out = {"unanswered": sum(1 for r in records if not r.ok), "_compared_items": 0}
    for r in records:
        number = _NUMBER_OF.get(r.req.op)
        if number is None or not r.ok:
            continue
        value, n = _wrongness(r, refs[r.req.tenant])
        out["_compared_items"] += n
        out[number] = (max(out.get(number, 0.0), value) if number == "quantile_rel_err"
                       else out.get(number, 0) + int(value))
    return out


def verdict(numbers: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the result line."""
    table = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()
             if not k.startswith("_")}
    return all(e["value"] <= e["limit"] for e in table.values()), table
