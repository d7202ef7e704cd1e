"""PRF — the paper's contribution: Parallel Random Forest in JAX.

Public surface:
  ForestConfig, Forest, GrowthState  core/types.py
  train_prf, PRFModel                core/api.py
  grow_forest_streamed               core/distributed.py (out-of-core sample
                                     blocks, one device or a mesh)
  make_prf_train_fn                  core/distributed.py (mesh-sharded)
"""
from .types import Forest, ForestConfig, GrowthState  # noqa: F401
from .api import PRFModel, train_prf  # noqa: F401
from .distributed import grow_forest_streamed  # noqa: F401
