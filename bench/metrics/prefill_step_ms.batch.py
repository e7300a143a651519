"""Mean wall time of a prefill step, from the benchmark span around
``ServeEngine.step`` (model step layer, ``jit_chunk_step``)."""
from bench import readers


def read(run):
    return readers.mean_span_ms(run, "prefill")
