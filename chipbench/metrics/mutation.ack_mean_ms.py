"""Stream, writer: mean time from ``submit_mutations`` to the batch's
acknowledgement (WAL frame fsynced, applied, epoch published), over every
batch the writer sent in the window, in ms.  The last batch's may come
after the close; the query load goes on until it does."""


def read(run):
    d = [ack - sub for sub, ack, _ in run.rec.batches]
    return 1e3 * sum(d) / len(d) if d else None
