"""Mean host time of a decode step: the benchmark span around
``ServeEngine.step`` minus the device time inside it (serve engine layer:
inputs, host argmax, dispatch)."""
from bench import readers


def read(run):
    return readers.mean_host_ms(run, "decode")
