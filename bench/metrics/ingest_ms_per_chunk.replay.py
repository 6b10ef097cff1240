"""Host time inside the chunk source's ``next()`` (the program's
re-bucketing of trace rows into a chunk), per chunk pulled in the window,
timed by the replay path's wrapper (host clock)."""


def read(run):
    chunks = run.work.get("chunks")
    if not chunks:
        return None
    return 1e3 * run.work["ingest_s"] / chunks
