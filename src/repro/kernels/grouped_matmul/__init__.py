from repro.kernels.grouped_matmul.grouped_matmul import expert_gmm
from repro.kernels.grouped_matmul.ref import expert_gmm_ref

__all__ = ["expert_gmm", "expert_gmm_ref"]
