"""Validating a chunk and putting it on the device, supervised, in ms per
chunk: the program's ``repro.stream.stage`` spans in the traced window
(profiler trace)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else \
        spans.span_ms_per_chunk(["repro.stream.stage"])
