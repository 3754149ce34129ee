"""Host seconds of the run's prepare spent building and placing the
shards: the running total of span `repro.prepare.shard` (each shard's
host build and its transfer to its chips), which the one prepare done in
set-up adds to.  None where the program has no such span."""


def read(run):
    try:
        from repro.core.tracing import span_totals
    except ImportError:
        return None
    rec = span_totals().get("repro.prepare.shard")
    return None if rec is None else rec["seconds"]
