"""Main-path kernels compile for a TPU v5e, at the widths the chip runs.

Compiled for a described (not attached) v5e with the TPU compiler, so a
kernel Mosaic refuses — a layout it cannot lower, more VMEM than a kernel
may use — fails here instead of on the chip. Nothing runs: results are the
interpret-mode tests' job (tests/test_kernels.py).
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# the full-size citeseer analogue (Table 1) and the device engine's default
# max_wave=256 member bits -> 8 uint32 frontier words per vertex
CITESEER_N = 693_947
FRONTIER_WORDS = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows,width", [(1024, 8), (1024, 16), (256, 128), (512, 1536)])
def test_label_intersect_compiles_for_v5e(one_chip, rows, width):
    """Every planner tier width of the citeseer analogue (8, 16), a wide tier,
    and a width past one slot block (VMEM stays bounded)."""
    hlo = _hlo(lambda a, b: ops.label_intersect(a, b, interpret=False), one_chip,
               ((rows, width), jnp.int32), ((rows, width), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows,slots", [(CITESEER_N // 3, 16), (1000, 4), (5, 16)])
def test_frontier_or_compiles_for_v5e(one_chip, rows, slots):
    """The ELL OR-gather with the whole full-size frontier in HBM."""
    hlo = _hlo(lambda nbr, f: ops.frontier_or(nbr, f, interpret=False),
               one_chip, ((rows, slots), jnp.int32),
               ((CITESEER_N, FRONTIER_WORDS), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("use_kernel", [True, False])
def test_fused_serve_program_compiles_for_v5e(one_chip, monkeypatch, use_kernel):
    """The serve path's fused program (prefilter, full-width gather,
    intersect) at the citeseer store's shape and the largest batch tile."""
    from repro.serve.engine import _tier_intersect_fused

    # the kernel, not its interpreter: ``label_intersect`` picks it while
    # tracing, so traces made on the CPU earlier in this process must go
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    n, width, rows = CITESEER_N, 16, 4096
    hlo = _hlo(lambda lo, li, meta, q: _tier_intersect_fused(lo, li, meta, q, use_kernel),
               one_chip, ((n, width), jnp.int32), ((n, width // 2), jnp.int32),
               ((n, 3), jnp.int32), ((rows, 2), jnp.int32))
    assert ("tpu_custom_call" in hlo) == use_kernel
