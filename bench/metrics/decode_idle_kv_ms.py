"""Device-idle time per decode step under the engine's ``serve.kv_gather``
and ``serve.kv_scatter`` spans: dispatch of the eager slot-cache gather and
scatter (serve engine layer)."""
from bench import program_trace as PT


def read(run):
    return PT.idle_ms(PT.load(run), "decode", ("kv_gather", "kv_scatter"))
