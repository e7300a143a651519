"""Device time per decode step of the programs other than
``jit_decode_step`` that start inside the engine's ``serve.decode`` span,
such as the logits slice (serve engine layer)."""
from bench import program_trace as PT


def read(run):
    return PT.eager_ms(PT.load(run), "decode", "jit_decode_step")
