"""Front end: mean wait of a traced query from its due time to the start
of the ``frontend.cohort`` span that served it, in ms.

A cohort span names its members: its own trace id and its links."""


def read(run):
    waits = []
    for s in run.spans:
        if s["name"] != "frontend.cohort":
            continue
        for t in [s["trace_id"], *s["links"]]:
            due = run.due_by_trace.get(t)
            if due is not None:
                waits.append(s["t_start"] - due)
    return 1e3 * sum(waits) / len(waits) if waits else None
