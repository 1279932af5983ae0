from repro.federated.simulation import (
    FLSimConfig,
    SimResult,
    run_fcf_simulation,
    run_seed_sweep,
    run_strategy_sweep,
)

__all__ = [
    "FLSimConfig", "run_fcf_simulation", "SimResult",
    "run_seed_sweep", "run_strategy_sweep",
]
