"""Compile the interval kernels for a described TPU v5e (no chip needed).

Interpret mode (tests/test_interval_step.py) checks what the kernels
compute; only the TPU compiler checks that Mosaic accepts their blocks,
memory spaces and scalar accesses.  Each test lowers a kernel with
``interpret=False`` onto one device of a described ``v5e:2x2`` topology
and compiles it, at the sweep's real width (B = 64 lanes, n = 65536
pages, 3 tiers) and at one odd shape (non-multiple-of-8 lanes, a page
count off the 128-lane tile, 2 tiers, an empty migration plan); the
migration kernel also at the edge of its SMEM budget, and the three
blocked kernels at 2**21 pages (4 TiB of 2 MiB pages), where a row
takes 16 page blocks.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles — a
compile for a described chip can be written to it but not read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.interval_step import kernel

WIDE = dict(B=64, n=65536, R=3)
ODD = dict(B=5, n=1000, R=2)
PLAN = 16384          # plan width of a k = 16384 policy at n = 65536
#: tier row + both plans fill ``kernel.SMEM_WORDS`` exactly
EDGE = dict(B=8, n=65536, R=3)
#: 18 lanes of 2,097,152 pages: rows far past one VMEM block
HUGE = dict(B=18, n=2097152, R=2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    hlo = jax.jit(lambda *a: fn(*a, interpret=False, **static)) \
        .lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


f32, i32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("shape", [WIDE, ODD, HUGE],
                         ids=["wide", "odd", "huge"])
def test_topk_mask_compiles(one_chip, shape):
    B, n = shape["B"], shape["n"]
    _compile(kernel.topk_mask_kernel, one_chip, ((B, n), f32), k=n // 4)


@pytest.mark.parametrize("shape,P,D", [(WIDE, PLAN, PLAN), (ODD, 0, 0),
                                       (EDGE, EDGE["n"], EDGE["n"])],
                         ids=["wide", "odd-empty-plan", "smem-budget-edge"])
def test_tier_migrate_compiles(one_chip, shape, P, D):
    B, n, R = shape["B"], shape["n"], shape["R"]
    assert kernel.tier_migrate_fits(n, P, D)
    _compile(kernel.tier_migrate_kernel, one_chip, ((B, n), i32),
             ((B, P), i32), ((B, D), i32), ((B, R), i32))


@pytest.mark.parametrize("shape", [WIDE, ODD, HUGE],
                         ids=["wide", "odd", "huge"])
def test_interval_account_compiles(one_chip, shape):
    B, n, R = shape["B"], shape["n"], shape["R"]
    _compile(kernel.interval_account_kernel, one_chip,
             ((B, R), f32), ((B, R), f32), ((B, R), f32), ((B,), f32),
             ((B, n), f32), ((B, n), i32), ((B, R - 1), f32),
             ((B, R - 1), f32), ((B, n), jnp.bool_), k=n // 4)


@pytest.mark.parametrize("shape", [WIDE, ODD, HUGE],
                         ids=["wide", "odd", "huge"])
def test_ewma_update_compiles(one_chip, shape):
    B, n = shape["B"], shape["n"]

    def ewma(s, l, c, a_s, w_s, *, interpret):
        return kernel.ewma_update_kernel(s, l, c, alpha_s=a_s, alpha_l=0.1,
                                         w_s=w_s, w_l=0.5,
                                         interpret=interpret)

    _compile(ewma, one_chip, ((B, n), f32), ((B, n), f32), ((B, n), f32),
             ((B,), f32), ((B,), f32))
