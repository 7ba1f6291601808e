"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers and compiles for a described v5e topology
(the TPU compiler is installed even where no chip is attached), which
refuses what interpret mode accepts — blocks off the (8, 128) tiling, too
much VMEM, a kernel the SPMD partitioner would have to split.  The
topology is described inside a fixture, so collecting this file loads no
TPU library and a host that cannot describe it skips these tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import photonics
from repro.dist import sharding
from repro.kernels import emu_matmul, ops

T, K, M = 4096, 1024, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("noise_mode", ["input", "prng"])
def test_photonic_matmul_compiles(one_chip, noise_mode):
    cfg = photonics.preset("offchip_bpd")

    def fn(a, b, key):
        return ops.photonic_matmul(a, b, cfg, key, noise_mode=noise_mode)

    _assert_kernel(fn, _sds((T, K), one_chip), _sds((M, K), one_chip),
                   _sds((2,), one_chip, jnp.uint32))


def test_dfa_gradient_compiles(one_chip):
    """The masked kernel, noise drawn in-kernel (the "input" mode's noise
    operand is the same for both kernels and compiles above)."""
    cfg = photonics.preset("offchip_bpd")

    def fn(a, b, mask, key):
        return ops.dfa_gradient(a, b, mask, cfg, key, noise_mode="prng")

    _assert_kernel(fn, _sds((T, K), one_chip), _sds((M, K), one_chip),
                   _sds((T, M), one_chip), _sds((2,), one_chip, jnp.uint32))


@pytest.mark.parametrize("preset", ["emu_ideal", "emu_onchip"])
def test_emu_fused_bank_compiles(one_chip, preset):
    """Noise off (emu_ideal) and on (emu_onchip: BPD read and shot noise
    drawn in-kernel, per-pass ADC)."""
    cfg = photonics.preset(preset)

    def fn(a, b, key):
        noisy = cfg.noise_std > 0
        return emu_matmul.fused_bank_product(
            a, b, cfg, jax.random.wrap_key_data(key) if noisy else None,
            impl="pallas", interpret=False)

    _assert_kernel(fn, _sds((T, K), one_chip), _sds((M, K), one_chip),
                   _sds((2,), one_chip, jnp.uint32))


def test_projection_runs_per_shard_under_a_mesh(topo):
    """Under a four-device data mesh the DFA projection's kernel runs on
    each device's quarter of the error rows: no gather of the batch in
    front of it, which a kernel the partitioner cannot split would need."""
    mesh = jax.make_mesh((4, 1), ("data", "model"), devices=topo.devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    cfg = photonics.preset("offchip_bpd")

    def fn(e, b, key):
        return photonics.photonic_project(e, b, cfg, key,
                                          backend=photonics.PallasBackend())

    with sharding.use_mesh(mesh):
        text = _assert_kernel(fn, _sds((8, T // 8, K), rows),
                              _sds((M, K), rep), _sds((2,), rep, jnp.uint32))
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert calls and all(f"[{T // 4},{K}]" in ln for ln in calls)
    assert "all-gather" not in text
