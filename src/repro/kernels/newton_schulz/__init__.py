from repro.kernels.newton_schulz.ops import newton_schulz_pallas, plan
from repro.kernels.newton_schulz.ref import reference_newton_schulz

__all__ = ["newton_schulz_pallas", "plan", "reference_newton_schulz"]
