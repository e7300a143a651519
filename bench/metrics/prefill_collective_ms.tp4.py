"""Device self time per prefill step of the collective operations (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and their async
halves) that start inside the engine's ``serve.prefill`` span, on the first
device (tensor-parallel collectives layer)."""
from bench import collectives


def read(run):
    return collectives.step_ms(run, "prefill")
