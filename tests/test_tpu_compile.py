"""The MM2IM kernel compiles for a TPU v5e, with no chip attached.

A ``v5e:2x2`` topology is described in a module fixture and ``mm2im`` is
lowered and compiled by Mosaic (``interpret=False``) for one of its chips
at the paper's Table II rows: DCGAN_1-4 at the batch sizes the server
warms, and every other row at batch 1, in f32 and int8.  Interpret mode
cannot see what these catch: blocks that break the (8, 128) tiling,
lowerings Mosaic lacks, and kernels that overrun their scoped VMEM
(``VMEM_LIMIT``, which Mosaic enforces at compile time).

StyleGAN2-256's six VALID 3x3 stride-2 up-convolutions compile too, at
the served batch of 8 in f32.

All compiles happen in this one file, inside tests: the TPU library is
loaded by one process at a time, so describing the topology at import
time would break the other test workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import TABLE_II
from repro.kernels.mm2im_db_pallas import mm2im_db_tconv
from repro.kernels.mm2im_ks_pallas import mm2im_ks_tconv
from repro.kernels.mm2im_og_pallas import mm2im_og_tconv
from repro.kernels.mm2im_pallas import (VMEM_BUDGET, mm2im_tconv,
                                        mm2im_vmem_bytes, plan_blocks)

V5E_HBM_BYTES = 16 * 10**9
ROWS = {r.name: r for r in TABLE_II}
DCGAN = ("DCGAN_1", "DCGAN_2", "DCGAN_3", "DCGAN_4")
CASES = ([(name, batch) for name in DCGAN for batch in (1, 8)]
         + [(r.name, 1) for r in TABLE_II if r.name not in DCGAN])
# No block of the single-buffered kernel fits the planner's budget here:
# the whole 256x256 input stays resident (about 36 MB in f32, modeled
# twice for double buffering).  Mosaic still compiles it under VMEM_LIMIT.
OVER_BUDGET = {("StyleTransfer_3", "f32")}
# StyleGAN2-256 (config-f): (input size, Ic, Oc) of each up-convolution.
STYLEGAN2_UP = [(4, 512, 512), (8, 512, 512), (16, 512, 512), (32, 512, 512),
                (64, 512, 256), (128, 256, 128)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off (its
    entries for a described chip cannot be read back, and warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["f32", "int8"])
@pytest.mark.parametrize("row,batch", CASES)
def test_mm2im_compiles_for_v5e(one_chip, row, batch, precision):
    r = ROWS[row]
    dtype = jnp.float32 if precision == "f32" else jnp.int8
    kw = dict(stride=r.stride, interpret=False)
    if precision == "int8":
        kw["out_scale"] = 0.01
    x = jax.ShapeDtypeStruct((batch, r.ihw, r.ihw, r.ic), dtype,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((r.ks, r.ks, r.oc, r.ic), dtype,
                             sharding=one_chip)
    compiled = jax.jit(lambda x, w: mm2im_tconv(x, w, **kw)).lower(
        x, w).compile()

    # Mosaic, not XLA or the interpreter, runs the layer: one kernel.
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    # The planner's blocks fit the VMEM it budgets for (Mosaic has already
    # held the kernel to the larger VMEM_LIMIT by compiling it) ...
    in_bytes = jnp.dtype(dtype).itemsize
    block_oh, block_oc = plan_blocks(r.ihw, r.ihw, r.ic, r.ks, r.oc,
                                     r.stride, "SAME", in_bytes=in_bytes,
                                     batch=batch)
    modeled = mm2im_vmem_bytes(r.ihw, r.ihw, r.ic, r.ks, r.oc, r.stride,
                               "SAME", block_oh=block_oh, block_oc=block_oc,
                               in_bytes=in_bytes, batch=batch)
    assert (modeled > VMEM_BUDGET) == ((row, precision) in OVER_BUDGET)
    # ... and the program's HBM footprint fits the chip.
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES


@pytest.mark.parametrize("tconv", [mm2im_tconv, mm2im_db_tconv,
                                   mm2im_ks_tconv, mm2im_og_tconv],
                         ids=["mm2im", "mm2im_db", "mm2im_ks", "mm2im_og"])
def test_int8_compiles_under_highest_matmul_precision(one_chip, tconv):
    """A caller's ``default_matmul_precision("highest")`` (the reference
    comparisons run under it) must not reach the int8 MXU product of any
    kernel family, which has no f32 contract precision."""
    r = ROWS["DCGAN_2"]
    x = jax.ShapeDtypeStruct((8, r.ihw, r.ihw, r.ic), jnp.int8,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((r.ks, r.ks, r.oc, r.ic), jnp.int8,
                             sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(lambda x, w: tconv(
            x, w, stride=r.stride, out_scale=0.01, interpret=False)).lower(
                x, w).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("ihw,ic,oc", STYLEGAN2_UP)
def test_stylegan2_valid_up_convolution_compiles_for_v5e(one_chip, ihw, ic,
                                                         oc):
    x = jax.ShapeDtypeStruct((8, ihw, ihw, ic), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, oc, ic), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x, w: mm2im_tconv(
        x, w, stride=2, padding="VALID", interpret=False)).lower(
            x, w).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    block_oh, block_oc = plan_blocks(ihw, ihw, ic, 3, oc, 2, "VALID",
                                     batch=8)
    assert mm2im_vmem_bytes(ihw, ihw, ic, 3, oc, 2, "VALID",
                            block_oh=block_oh, block_oc=block_oc,
                            batch=8) <= VMEM_BUDGET
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (8, 2 * ihw + 1, 2 * ihw + 1, oc)
