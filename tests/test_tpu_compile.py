"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers and compiles for a described v5e topology
(the TPU compiler is installed even where no chip is attached), which
refuses what interpret mode accepts — blocks off the (8, 128) tiling, too
much VMEM, a kernel the SPMD partitioner would have to split.  The
topology is described inside a fixture, so collecting this file loads no
TPU library and a host that cannot describe it skips these tests.
"""

import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import photonics
from repro.dist import sharding
from repro.kernels import emu_matmul, flash_attention, ops

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


def _emu_bank_reader():
    """The benchmark's signature match for the fused emu kernel's calls."""
    path = Path(__file__).resolve().parents[1] / "perfbench/kernels/emu_bank.py"
    spec = importlib.util.spec_from_file_location("perfbench_emu_bank", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("t,k,m", [
    (8192, 1024, 1024),   # qwen1.5-0.5b's DFA projection, 8 × 1024 tokens
    (64, 10, 800),        # the paper's MLP: a batch of 64, DFA error of 10
    (8, 1024, 151936),    # a decode step through qwen's head: lane blocks
])
def test_emu_bank_calls_match_the_benchmark_signature(one_chip, t, k, m):
    """Each compiled call of the fused emu kernel, printed with its operand
    shapes as a profile's op text carries them, passes the benchmark's
    ``match``: a call the match missed would leave the kernel's time and
    roofline share unread."""
    cfg = photonics.preset("emu_onchip")

    def fn(a, b, key):
        return emu_matmul.fused_bank_product(
            a, b, cfg, jax.random.wrap_key_data(key), impl="pallas",
            interpret=False)

    compiled = jax.jit(fn).lower(
        _sds((t, k), one_chip), _sds((m, k), one_chip),
        _sds((2,), one_chip, jnp.uint32)).compile()
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = "\n".join(mod.to_string(opts) for mod in
                     compiled.runtime_executable().hlo_modules())
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    match = _emu_bank_reader().match
    assert calls and all(match(ln) for ln in calls)


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


def _kernel_cases(one_chip):
    """name -> (fn, argument shapes) of each Pallas kernel, at small widths."""
    bpd, emu = photonics.preset("offchip_bpd"), photonics.preset("emu_onchip")
    key = _sds((2,), one_chip, jnp.uint32)
    a, b = _sds((256, 128), one_chip), _sds((256, 128), one_chip)
    heads = _sds((1, 2, 256, 64), one_chip)
    return {
        "flash_attention": (flash_attention.flash_attention, (heads, heads, heads)),
        "emu_bank": (lambda a, b, key: emu_matmul.fused_bank_product(
            a, b, emu, jax.random.wrap_key_data(key), impl="pallas",
            interpret=False), (a, b, key)),
        "photonic_matmul": (lambda a, b, key: ops.photonic_matmul(
            a, b, bpd, key, noise_mode="prng"), (a, b, key)),
    }


@pytest.mark.parametrize("kernel", ["emu_bank", "photonic_matmul", "flash_attention"])
def test_kernel_name_in_op_text(one_chip, kernel, monkeypatch):
    """Each kernel's call names it in its op text: in ``kernel_metadata``,
    after the operands, which a profile's op event carries (and which a
    signature match on the operands does not reach), and in the op's
    ``op_name`` (``pallas_call``'s ``name``); no other kernel's name is
    there."""
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    cases = _kernel_cases(one_chip)
    fn, args = cases[kernel]
    text = _assert_kernel(fn, *args)
    # one HLO instruction each; the metadata's JSON spans lines
    ops = re.split(r"\n(?= *(?:ROOT )?%)", text)
    calls = [op.strip() for op in ops if 'custom_call_target="tpu_custom_call"' in op]
    assert calls
    for op in calls:
        assert f'/{kernel}/pallas_call"' in op
        assert op.index(f'"kernel":"{kernel}"') > op.index("custom-call(")
        assert not [k for k in cases if k != kernel and f'"kernel":"{k}"' in op]


def _reader(kernel: str):
    """The benchmark's match (and cost) for one kernel's calls."""
    path = Path(__file__).resolve().parents[1] / f"perfbench/kernels/{kernel}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_{kernel}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _moe_gmm_reader():
    """The benchmark's match for the grouped expert matmul's calls."""
    return _reader("moe_gmm")



def test_moe_gmm_compiles_at_the_moonlight_cell_shape(one_chip, monkeypatch):
    """The grouped expert matmul, its product and both vjp products, at the
    Moonlight cell's shape (2 × 8192 tokens × top-6 = 98,304 buffer rows,
    8 held experts of width 1408 at d=2048): each call compiles for the
    chip, names itself in its op text, and passes the benchmark's match."""
    from repro.kernels import moe_gmm

    monkeypatch.setattr(moe_gmm, "_on_tpu", lambda: True)
    m, d, f, g = 98304, 2048, 1408, 8

    def fn(x, w1, w2, sizes, ct):
        def layer(x, w1, w2):
            h = moe_gmm.grouped_matmul(x, w1, sizes)
            return moe_gmm.grouped_matmul(jax.nn.silu(h[:, :f]) * h[:, f:], w2, sizes)

        y, vjp = jax.vjp(layer, x, w1, w2)
        return y, vjp(ct)

    text = _assert_kernel(fn, _sds((m, d), one_chip), _sds((g, d, 2 * f), one_chip),
                          _sds((g, f, d), one_chip), _sds((g,), one_chip, jnp.int32),
                          _sds((m, d), one_chip))
    ops = re.split(r"\n(?= *(?:ROOT )?%)", text)
    calls = [op.strip() for op in ops if 'custom_call_target="tpu_custom_call"' in op]
    assert len(calls) == 6
    names = sorted(re.search(r'"kernel":"(moe_gmm\w*)"', op).group(1) for op in calls)
    assert names == ["moe_gmm"] * 2 + ["moe_gmm_dlhs"] * 2 + ["moe_gmm_drhs"] * 2
    match = _moe_gmm_reader().match
    assert all(match(op) for op in calls)


def _op_texts(compiled):
    """Each kernel call's op text as a profile carries it: the operands'
    shapes and the kernel's metadata (which spans lines)."""
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = "\n".join(mod.to_string(opts) for mod in
                     compiled.runtime_executable().hlo_modules())
    ops = re.split(r"\n(?= *(?:ROOT )?[\w.-]+ = )", text)
    return [op.strip() for op in ops if 'custom_call_target="tpu_custom_call"' in op]


@pytest.mark.parametrize("b,h,s,dk,dv", [
    (8, 16, 1024, 64, 64),     # qwen1.5-0.5b: 8 × 1024 tokens, 16 heads of 64
    (2, 16, 8192, 192, 128),   # Moonlight's MLA: 2 × 8192, q/k 192, v 128
])
def test_flash_attention_compiles_at_the_cell_shapes(one_chip, b, h, s, dk, dv, monkeypatch):
    """The flash kernel's forward and its vjp at each training cell's
    shape compile for the chip; each call is found by the benchmark's
    ``flash_attention`` match and by no other kernel's, ``emu_bank``'s
    signature match included, so ``kernel_ms.emu_bank`` reads what it did."""
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)

    def fn(q, k, v, ct):
        o, vjp = jax.vjp(lambda q, k, v: flash_attention.flash_attention(q, k, v), q, k, v)
        return o, vjp(ct)

    qk, vs = _sds((b, h, s, dk), one_chip), _sds((b, h, s, dv), one_chip)
    compiled = jax.jit(fn).lower(qk, qk, vs, vs).compile()
    calls = _op_texts(compiled)
    names = sorted(re.search(r'"kernel":"(\w+)"', op).group(1) for op in calls)
    assert names == ["flash_attention", "flash_attention_bwd"]
    others = sorted(p.stem for p in (Path(__file__).resolve().parents[1]
                                      / "perfbench/kernels").glob("*.py"))
    assert {"emu_bank", "moe_gmm", "flash_attention"} <= set(others)
    for op in calls:
        assert [k for k in others if _reader(k).match(op)] == ["flash_attention"]
