from repro.train.elastic import rescale_lmc_state
from repro.train.health import (FaultPlan, HealthConfig, HealthGuard,
                                PipelineFault, SimulatedPreemption,
                                StalenessBudgetError, TrainingDivergedError)
from repro.train.loop import GNNTrainer

__all__ = ["GNNTrainer", "FaultPlan", "HealthConfig", "HealthGuard",
           "PipelineFault", "SimulatedPreemption", "StalenessBudgetError",
           "TrainingDivergedError", "rescale_lmc_state"]
