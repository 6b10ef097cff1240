"""Share of the traced window in which the device ran no operation while
the program was inside a chunk boundary's checkpoint
(``repro.stream.checkpoint``), in % (profiler trace).  Never above
``device_idle_pct.replay``."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else \
        spans.idle_pct_under("repro.stream.checkpoint")
