"""Compile the main path's Pallas kernels for a described TPU v5e chip.

The TPU compiler is installed wherever JAX's TPU library is, and it compiles
for a chip that is described, not attached — so these tests catch what
interpret mode cannot (tiling, VMEM, scalar/vector memory rules) with no
chip.  They compile; nothing runs, so they say nothing about results or
times.  The topology is described inside a module-scoped fixture (never at
import), and every compile happens in the test's own process with the
persistent compile cache off, since an entry compiled for a described chip
cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_decode_attention_op
from repro.kernels.support_core.support_core_kernel import fused_step_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("Q,C,N,R", [
    (12, 2, 512, 3),     # chip_smoke admission burst (kv + scratch + refill)
    (8, 2, 512, 3),      # chip_smoke decode burst (malloc + refill)
    (64, 8, 65536, 4),   # DESIGN.md §8's largest shape
])
def test_support_core_kernel_compiles_for_v5e(one_chip, Q, C, N, R):
    q = _spec((Q,), jnp.int32, one_chip)
    cn = _spec((C, N), jnp.int32, one_chip)
    c1 = _spec((C,), jnp.int32, one_chip)
    step = jax.jit(lambda *a: fused_step_kernel(*a, max_per_req=R))
    _check(step.lower(q, q, q, q, cn, c1, cn, cn, c1, c1, c1, c1, c1)
           .compile())


def test_paged_attention_kernel_compiles_for_v5e(one_chip):
    """deepseek-7b decode widths: 32 heads over 32 kv heads, head_dim 128,
    bf16 pages of 16 tokens, 4 lanes of 17 page slots."""
    B, H, KV, hd, ps, P, pages = 4, 32, 32, 128, 16, 17, 512
    bf16 = jnp.bfloat16
    compiled = paged_decode_attention_op.lower(
        _spec((B, H, hd), bf16, one_chip),
        _spec((pages, ps, KV, hd), bf16, one_chip),
        _spec((pages, ps, KV, hd), bf16, one_chip),
        _spec((B, P), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    _check(compiled)
