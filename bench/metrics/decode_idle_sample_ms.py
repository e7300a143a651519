"""Device-idle time per decode step under the engine's ``serve.sample``
span: logits to the host, host argmax, emit (serve engine layer)."""
from bench import program_trace as PT


def read(run):
    return PT.idle_ms(PT.load(run), "decode", ("sample",))
