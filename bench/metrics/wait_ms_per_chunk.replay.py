"""The host blocked on the device's compute, in ms per chunk: the
pipeline's drain (``repro.stream.wait``) plus the checkpoint's wait for
the chunk it saves (``repro.ckpt.wait``), each per chunk, in the traced
window (profiler trace)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else \
        spans.span_ms_per_chunk(["repro.stream.wait", "repro.ckpt.wait"])
