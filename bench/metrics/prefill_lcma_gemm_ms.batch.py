"""Device self time per prefill step of the leaf operations under
``falcon.lcma_gemm``, the R-batched GEMM of an LCMA (FalconGEMM dispatch and
LCMA execution layer)."""
from bench import program_trace as PT


def read(run):
    return PT.stage_ms(PT.load(run), "prefill", ("falcon.lcma_gemm",))
