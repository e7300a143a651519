"""Mean wall time of a decode step, from the benchmark span around
``ServeEngine.step`` (model step layer, ``jit_decode_step`` over the mesh)."""
from bench import readers


def read(run):
    return readers.mean_span_ms(run, "decode")
