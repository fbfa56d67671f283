"""Core contribution of the paper: RandomizedCCA and its baselines."""

from .exact import (CCASolution, cca_objective, exact_cca, feasibility_errors,
                    streamed_feasibility_errors)
from .horst import HorstConfig, HorstResult, horst_cca
from .rcca import (
    RCCAConfig,
    RCCAResult,
    randomized_cca,
    randomized_cca_iterator,
    randomized_cca_streaming,
)

__all__ = [
    "CCASolution",
    "cca_objective",
    "exact_cca",
    "feasibility_errors",
    "streamed_feasibility_errors",
    "HorstConfig",
    "HorstResult",
    "horst_cca",
    "RCCAConfig",
    "RCCAResult",
    "randomized_cca",
    "randomized_cca_iterator",
    "randomized_cca_streaming",
]
