"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology. Each test compiles one kernel with
``interpret=False`` at the widths ``chip_smoke.py`` trains and serves
at — HIGGS's 28 features, 64 bins, 2 classes, a 32-tree chunk, the
depth-8 frontier S=256 and its sibling-reuse width S=128, the 2,049-row
node pool, and the one-slot root pass (S=1) of dimension reduction; the
histogram kernel also at S=64, a frontier within one MXU tile — and
checks that

* the kernel compiles with its scoped VMEM capped at the estimate its
  block chooser budgets, so the estimate bounds what the compiler
  allocates;
* it does not compile under half that cap, so the estimate stays within
  a factor of two of the compiler's need and the chooser does not
  shrink the blocks for room the kernel never uses;
* the compiled program holds the kernel (``tpu_custom_call``) and fits
  the chip's HBM (``memory_analysis``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.kernels.gain_ratio.kernel import (
    choose_blocks, hist_vmem_bytes, multi_tree_hist_pallas,
)
from repro.kernels.split_scan.kernel import (
    choose_score_block, score_vmem_bytes, split_scan_block,
)
from repro.kernels.tree_traverse.kernel import (
    choose_traverse_block, traverse_block, traverse_vmem_bytes,
)

N, F, B, C, TC, P = 1_000_000, 28, 64, 2, 32, 2049
HBM_BYTES = 16 * 2 ** 30          # TPU v5e: 16 GiB of HBM per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_on_chip(fn, args, estimate, monkeypatch):
    from jax.experimental.pallas import tpu as pltpu

    pallas_call = pl.pallas_call
    cap = {}

    def capped(*a, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=cap["bytes"])
        return pallas_call(*a, **kw)

    monkeypatch.setattr(pl, "pallas_call", capped)

    def compile_capped(limit):
        cap["bytes"] = int(limit)
        # A fresh function object, so the capped call is traced anew.
        return jax.jit(lambda *a: fn(*a)).lower(*args).compile()

    compiled = compile_capped(estimate)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    with pytest.raises(jax.errors.JaxRuntimeError, match="memory space vmem"):
        compile_capped(estimate // 2)


@pytest.mark.parametrize("S", [256, 128, 64, 1])
def test_hist_kernel_compiles_for_v5e(one_chip, monkeypatch, S):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_blk, f_blk = choose_blocks(N, F, S, B, C)
    _compile_on_chip(
        functools.partial(
            multi_tree_hist_pallas, n_slots=S, n_bins=B, interpret=False,
        ),
        (sds((N, F), jnp.uint8), sds((N, C)), sds((TC, N)), sds((TC, N), jnp.int32)),
        hist_vmem_bytes(n_blk, f_blk, S, B, C), monkeypatch,
    )


@pytest.mark.parametrize("S", [256, 128])
def test_split_scan_kernel_compiles_for_v5e(one_chip, monkeypatch, S):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f_blk = choose_score_block(S, B, C, F)
    _compile_on_chip(
        lambda h, m, f0: split_scan_block(h, m, None, f0, interpret=False),
        (sds((TC, S, F, B, C)), sds((TC, F), jnp.bool_), sds((), jnp.int32)),
        score_vmem_bytes(f_blk, S, B, C), monkeypatch,
    )


@pytest.mark.parametrize("n", [1024, 100_000])
def test_traverse_kernel_compiles_for_v5e(one_chip, monkeypatch, n):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_blk = choose_traverse_block(P, F, C)
    _compile_on_chip(
        functools.partial(traverse_block, carry=None, depth=8, interpret=False),
        (sds((n, F), jnp.uint8), sds((TC, P)), sds((TC, P)), sds((TC, P)),
         sds((TC, P, C), jnp.float32)),
        traverse_vmem_bytes(n_blk, P, F, C), monkeypatch,
    )
