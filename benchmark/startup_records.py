"""The program's own record of its start-up, as the eight readers that move
``setup_s`` see it.

The program writes a record round each thing it does once a process or once
a compile (``paddle_tpu.obs.trace`` phase records: ``startup.import``,
``startup.param_init``, ``aot.lower`` / ``aot.key`` / ``aot.load`` /
``aot.compile`` / ``aot.store``, ``trainstep.first_execute``, and ``jax.*``
for every program jax traces, lowers, compiles or loads), each with an id and
the parent that was open on its thread. Here: those that ended before the
window's first stamp, on the window's clock (``time.perf_counter``), each
with its ancestors, and the six named durations, which count no second
twice: a record counts for its metric only where none of its ancestors
counts for any of the six. A program that writes no such record (the parent
of the PR that brought them) reads 0.0 everywhere.
"""


def _trainstep(rec):
    return rec["args"].get("site") == "trainstep"


# metric -> which records it reads; each is its records' whole duration,
# children included, and ``small_programs_s`` takes what none of the others
# holds: the optimizer's slots, lr and key, the benchmark's own norms
CLAIMS = {
    "import_s": lambda r: r["name"] == "startup.import",
    "param_init_s": lambda r: r["name"] == "startup.param_init",
    "step_lower_s": lambda r: r["name"] in ("aot.lower", "aot.key")
    and _trainstep(r),
    "step_load_s": lambda r: r["name"] in ("aot.load", "aot.compile",
                                           "aot.store") and _trainstep(r),
    "step_first_execute_s": lambda r: r["name"] == "trainstep.first_execute",
    "small_programs_s": lambda r: r["name"].startswith("jax."),
}


def claim(rec):
    """The one metric that reads this record, or None."""
    return next((m for m, reads in CLAIMS.items() if reads(rec)), None)


def program_records():
    """Every record the program holds, with ``start`` and ``end`` in seconds
    on ``time.perf_counter``; none where the program has no such records."""
    from paddle_tpu.obs import trace

    clock = getattr(trace, "to_perf_counter", None)
    if clock is None:
        return []
    return [dict(e, start=clock(e["ts"]), end=clock(e["ts"] + e["dur"]))
            for e in trace.trace_events() if "id" in e]


def before(records, cut):
    """The records that ended by ``cut``, each with its ``ancestors``
    (records, nearest first)."""
    by_id = {r["id"]: r for r in records}

    def ancestors(rec):
        out, seen = [], {rec["id"]}
        while rec.get("parent") in by_id and rec["parent"] not in seen:
            rec = by_id[rec["parent"]]
            seen.add(rec["id"])
            out.append(rec)
        return out

    return [dict(r, ancestors=ancestors(r)) for r in records
            if r["end"] <= cut]


def timeline(records, cut):
    """(records counted, import's start or None): of the records that ended
    by ``cut`` on the thread that imported the program (another thread's
    time runs beside it, not in it), those that count for a metric: claimed,
    and under no claimed ancestor."""
    early = before(records, cut)
    imported = next((r for r in early if claim(r) == "import_s"), None)
    if imported is not None:
        early = [r for r in early if r["tid"] == imported["tid"]]
    counted = [r for r in early if claim(r) and
               not any(claim(a) for a in r["ancestors"])]
    return counted, imported and imported["start"]


def named_seconds(records, cut):
    """metric -> seconds, for the six named durations: disjoint, by the
    ancestor rule, so their sum is time of the importing thread."""
    out = dict.fromkeys(CLAIMS, 0.0)
    for rec in timeline(records, cut)[0]:
        out[claim(rec)] += rec["end"] - rec["start"]
    return out


def unnamed_seconds(records, cut):
    """From ``startup.import``'s start to ``cut``, less the six named
    durations: what the timeline does not name (the benchmark's rows and
    weights, ``TrainStep.__init__``, the checked steps' own execution and
    reads, the warm-up)."""
    counted, began = timeline(records, cut)
    if began is None:
        return 0.0
    return cut - began - sum(r["end"] - r["start"] for r in counted)


def read(window, metric):
    """A reader's whole body: one of the six named durations before the
    window's start, or ``setup_unnamed_s``."""
    records, cut = program_records(), window.stamps[0]
    if metric == "setup_unnamed_s":
        return unnamed_seconds(records, cut)
    return named_seconds(records, cut)[metric]
