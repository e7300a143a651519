"""Device self time per decode step of the collective operations (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and their async
halves) that start inside the engine's ``serve.decode`` span, on the first
device (tensor-parallel collectives layer)."""
from bench import collectives


def read(run):
    return collectives.step_ms(run, "decode")
