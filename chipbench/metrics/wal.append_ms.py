"""Stream, WAL: mean duration of the ``mutation.wal_append`` span (frame,
write and fsync of one batch), in ms."""


def read(run):
    d = [s["duration_s"] for s in run.spans
         if s["name"] == "mutation.wal_append" and s["duration_s"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
