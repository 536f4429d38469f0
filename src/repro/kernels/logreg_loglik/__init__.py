from repro.kernels.logreg_loglik.ops import lane_dense, logreg_loglik_grad, use_fused
from repro.kernels.logreg_loglik.ref import logreg_loglik_grad_ref

__all__ = ["lane_dense", "logreg_loglik_grad", "logreg_loglik_grad_ref", "use_fused"]
