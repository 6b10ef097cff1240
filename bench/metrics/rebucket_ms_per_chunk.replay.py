"""The program's re-bucketing of trace rows into one chunk, in ms per
chunk: its ``repro.ingest.chunk`` spans in the traced window (profiler
trace).  The program's own side of ``ingest_ms_per_chunk.replay``."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else \
        spans.span_ms_per_chunk(["repro.ingest.chunk"])
