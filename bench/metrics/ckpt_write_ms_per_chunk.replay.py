"""The checkpoint's durable write (npz, SHA-256, manifest, rename), in ms
per chunk: the program's ``repro.ckpt.write`` spans in the traced window
(profiler trace)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else \
        spans.span_ms_per_chunk(["repro.ckpt.write"])
