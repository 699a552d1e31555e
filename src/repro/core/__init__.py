"""LMC core: the paper's primary contribution.

  history.py   — historical embedding / auxiliary-variable stores (H̄, V̄)
  methods.py   — LMC / GAS / Cluster-GCN / ablations as one config space
  lmc.py       — Algorithm 1: compensated forward + message-passing backward
  exact.py     — full-batch ground truth, exact adjoints, backward-SGD (Thm 1)
  distributed.py — pjit/shard_map multi-device LMC step (one cluster/device)
"""
from repro.core.history import HistoricalState, init_history
from repro.core.methods import (MBMethod, METHODS, LMC, GAS, CLUSTER, CF_ONLY,
                                CB_ONLY, TI, RHO_BUDGET_DEFAULT)
from repro.core.lmc import (Batch, batch_fills, host_batch, make_infer_step,
                            make_train_step, to_device_batch)
from repro.core.exact import (FullGraphData, from_graph, full_loss, full_grads,
                              accuracy, exact_layer_values, backward_sgd_grads)

__all__ = [
    "HistoricalState", "init_history", "MBMethod", "METHODS",
    "LMC", "GAS", "CLUSTER", "CF_ONLY", "CB_ONLY", "TI", "RHO_BUDGET_DEFAULT",
    "Batch", "batch_fills", "host_batch", "make_infer_step", "make_train_step",
    "to_device_batch",
    "FullGraphData", "from_graph", "full_loss", "full_grads", "accuracy",
    "exact_layer_values", "backward_sgd_grads",
]
