"""Compile-only checks of the crossbar kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e and refuses what the chip would refuse (blocks off
the (8, 128) tiling, primitives Mosaic cannot lower, shape casts, fast
memory over the limit).  These tests compile the main path's three
crossbar kernels at a decode-sized (256 packets) and a prefill-sized
(8192 packets = 4096 tokens x top-2) offer with 4096-wide bf16 packets and
check that each is a Mosaic custom call.  Nothing runs; results are pinned
by the interpret-mode tests in ``test_kernels.py`` and ``test_fabric.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a worker that decides at import
which tests exist would give the workers different collections.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossbar_dispatch import kernel as K

N_PORTS = 8
D_MODEL = 4096
BLOCK_T = 256


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:              # no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _lowered(kernel: str, T: int, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = s((T,), jnp.int32)
    capacity = 8 * -(-5 * T // (32 * N_PORTS))     # 1.25x mean port load
    if kernel == "plan_multi":
        regs = s((N_PORTS, N_PORTS), jnp.int32)
        return K.plan_multi_call.lower(i32, i32, regs, regs,
                                       n_ports=N_PORTS, block_t=BLOCK_T)
    if kernel == "scatter":
        return K.scatter_call.lower(
            s((T, D_MODEL), jnp.bfloat16), i32, i32, i32, n_ports=N_PORTS,
            capacity=capacity, block_t=BLOCK_T)
    return K.combine_call.lower(
        s((N_PORTS, capacity, D_MODEL), jnp.bfloat16), i32, i32, i32,
        s((T,), jnp.float32), block_t=BLOCK_T)


@pytest.mark.parametrize("T", [256, 8192])
@pytest.mark.parametrize("kernel", ["plan_multi", "scatter", "combine"])
def test_crossbar_kernel_compiles_for_v5e(kernel, T, one_chip):
    hlo = _lowered(kernel, T, one_chip).compile().as_text()
    assert "tpu_custom_call" in hlo
