"""What decides `correct`: every answer the window produced, compared
with the plain reference once the window has closed. Each number has a
limit of its own; the readings the limits were set from are in
../PERF.md. An exact comparison has the limit 0; the quantiles' limit is
the one the configuration states (the sketch's documented error).

Where the configuration lets the compactor run inside the window
(`compaction_in_run`), an additive answer is right when ONE state of the
store (reference.py: a partition of the loaded blocks into compaction
outputs) gives all of it, every series and both quantiles: a query's jobs
are cut from one blocklist. The comparison stays exact. A merge is never
undone, so a count that began after another had ended may not need fewer
merges than that one (`_went_back`); and run.py gives a tenant whose
blocks no job has touched by the window's end the one state it has.
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
    "readback_wrong": 0,    # acknowledged traces read back with other spans, in the window or after
    "span_count_gap": 0,    # acknowledged spans the store does not count
    "server_exit": 0,       # the server's exit code after POST /shutdown
    "log_errors": 0,        # ERROR or CRITICAL lines in the server's log over the whole run
}

_NUMBER_OF = {"find": "find_wrong", "find_acked": "readback_wrong", "search_tags": "search_wrong",
              "traceql_filter": "traceql_wrong", "rate_by_name": "count_wrong",
              "rate_total": "count_wrong", "rate_by_service": "count_wrong",
              "quantiles": "quantile_rel_err"}


def _counts_equal(got: dict, want: dict) -> bool:
    return (set(got) == set(want)
            and all(abs(got[k] - want[k]) < 1e-3 for k in want))


def _quantile_err(r, want: dict) -> float:
    """The worst relative error of the answer's quantiles against `want`'s
    order statistics. One non-empty step holds every span: anything else
    is an error of 100 %."""
    return max(min(abs(got[0] - t) / t for t in true) if len(got) == 1 else 1.0
               for true, got in ((true, r.answer.get(q, [])) for q, true in want.items()))


def _wrongness(r, ref) -> tuple:
    """(how wrong the answer is, how many items were compared)."""
    op, args = r.req.op, r.req.args
    if op == "quantiles":
        from traffic import QUANTILES

        # against the nearer state's order statistics
        return min(_quantile_err(r, state.quantiles(*args, QUANTILES))
                   for state in ref.states), len(QUANTILES)
    if op == "find_acked":  # the push's own spans, whatever the store was loaded with
        return r.answer != args[1], len(args[1])
    want = getattr(ref, op)(*args)  # find: None for an absent id, as a 404 parses
    return r.answer != want, len(want or ())


def _explained_by(r, ref) -> tuple:
    """(the merges behind every state of the store that gives the whole of
    a `rate_*` answer, how many items were compared). Without the key the
    store has one state, the un-compacted one."""
    wants = [getattr(state, r.req.op)(*r.req.args) for state in ref.states]
    return [state.merges for state, want in zip(ref.states, wants)
            if _counts_equal(r.answer, want)], len(wants[0])


def _went_back(counted: list) -> int:
    """The answers that only a store gone back explains: a merge is never
    undone, so an answer that began after another had ended is held to a
    state with as many merges as the fewest that explain the earlier one.
    `counted` holds (t0, t1, fewest, most merges) of each explained answer."""
    ended = sorted(counted, key=lambda c: c[1])
    wrong = floor = i = 0
    for t0, _, _, most in sorted(counted):
        while i < len(ended) and ended[i][1] < t0:
            floor = max(floor, ended[i][2])
            i += 1
        wrong += most < floor
    return wrong


def compare(records: list, refs: dict) -> dict:
    """{number: value} over the answered requests of the window. `refs`
    maps a tenant to its Reference (or to a control in its place)."""
    out = {"unanswered": sum(1 for r in records if not r.ok), "_compared_items": 0}
    counted: dict = {}  # tenant -> its explained `rate_*` answers, for _went_back
    for r in records:
        number = _NUMBER_OF.get(r.req.op)
        if r.req.op == "find_acked" and not r.ok:  # acknowledged, and not there: wrong, not only late
            out[number] = out.get(number, 0) + 1
        if number is None or not r.ok:
            continue
        ref = refs.get(r.req.tenant)  # none where nothing was loaded: only pushes' own finds
        if r.req.op.startswith("rate_"):
            merges, n = _explained_by(r, ref)
            value = not merges
            if merges:
                counted.setdefault(r.req.tenant, []).append(
                    (r.t0, r.t1, min(merges), max(merges)))
        else:
            value, n = _wrongness(r, ref)
        out["_compared_items"] += n
        out[number] = (max(out.get(number, 0.0), value) if number == "quantile_rel_err"
                       else out.get(number, 0) + int(value))
    for answers in counted.values():
        out["count_wrong"] += _went_back(answers)
    return out


def verdict(numbers: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the result line."""
    table = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()
             if not k.startswith("_")}
    return all(e["value"] <= e["limit"] for e in table.values()), table
