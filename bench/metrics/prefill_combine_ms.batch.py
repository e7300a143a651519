"""Device self time per prefill step of the leaf operations under
``falcon.combine_a`` and ``falcon.combine_h`` (FalconGEMM dispatch and LCMA
execution layer)."""
from bench import program_trace as PT


def read(run):
    return PT.stage_ms(PT.load(run), "prefill",
                       ("falcon.combine_a", "falcon.combine_h"))
