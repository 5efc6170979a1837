# Pallas TPU kernels for the TNN compute hot-spots (the layers TNNGen's
# silicon implements with unary temporal logic):
#   fused_column    — the training hot path: RNL fire + k-WTA + expected STDP
#                     in ONE kernel invocation, scanned over epochs x volleys
#                     with resident weights (in-kernel plane decomposition)
#   rnl_response    — fused RNL potential + first-crossing (one-hot plane MXU matmuls)
#   stdp_update     — fused per-synapse STDP case-select/stabilize/clamp (VPU)
# Each has a pure-jnp oracle in ref.py; ops.py holds the jit'd wrappers.
# Execution policy (Mosaic vs interpreter vs reference lowering) is decided
# in ONE place: repro.core.backend — kernels never default interpret=True.
from repro.kernels import fused_column, ops, ref  # noqa: F401
from repro.kernels.rnl_response import rnl_fire_pallas  # noqa: F401
from repro.kernels.stdp_update import stdp_update_pallas  # noqa: F401
