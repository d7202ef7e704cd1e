"""Pallas kernels of the PRF hot path: T_GR histograms (``gain_ratio``),
T_NS split scoring (``split_scan``) and fused prediction
(``tree_traverse``), each with a pure-XLA oracle in its ``ref.py``."""
import jax


def default_interpret() -> bool:
    """Interpret-mode emulation off-TPU, compiled on TPU — the one
    resolution rule every kernel caller shares."""
    return jax.default_backend() != "tpu"
