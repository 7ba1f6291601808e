"""Photonic execution model: MRR weight-bank matrix products with the
paper's measured noise, precision, and tiling semantics.

The physical machine (paper §2–3):

* An M×N MRR weight bank computes M inner products of length N per
  operational cycle; weights (and the encoded inputs) live in [-1, 1].
* Larger matrices are subdivided by a GeMM compiler into bank-sized panels
  processed over multiple cycles (paper §3).
* Every analog inner product carries Gaussian read noise.  Measured:
  σ = 0.019 (single MRR multiply), 0.098 (1×4 bank + off-chip BPD),
  0.202 (on-chip BPD) — in *full-scale output* units where the output
  range is [-1, 1]  ⇒  effective bits = log2(2/σ).

TPU adaptation (DESIGN.md §2): we do not tile the contraction by the
physical bank width (20) — that would waste the 128-wide MXU.  Instead the
Pallas kernel tiles by MXU-aligned blocks and draws noise with variance
σ²·(block_k / bank_cols), statistically identical to accumulating
block_k/bank_cols physical bank passes.  The *pure-JAX reference path*
(this module) draws the total accumulated noise once:

    C = A @ Bᵀ + η,   η ~ N(0, σ² · ceil(K / bank_cols))  (per element)

Noise conventions:
* "absolute"  — σ is added per bank pass in the operands' natural units;
  this is the paper's own MNIST-simulation protocol ("adds accurately
  scaled Gaussian noise ... to the output of each MAC operation").
* "fullscale" — σ is relative to the bank's full-scale output (N_bank·s_A·s_B
  for normalised operands): physically conservative; noise grows with
  operand magnitude.  Both are available; "absolute" is the default because
  it is what reproduces the paper's Fig. 5 numbers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import annotate, batch_axes, current_mesh
from repro.hardware.mrr import MRRConfig
from repro.lint.runtime import check_finite
from repro.utils import prng


@dataclasses.dataclass(frozen=True)
class PhotonicConfig:
    bank_rows: int = 50  # M — rows of MRR arrays (paper headline bank 50×20)
    bank_cols: int = 20  # N — WDM channels per waveguide bus
    # Parallel WDM buses (paper §5 scale-out): each bus is a full physical
    # bank (rows×cols rings) with its own modulator/DAC and BPD/ADC chain.
    # The GeMM compiler schedules contraction panels across buses in the
    # same operational cycle, so throughput scales ~linearly while the
    # accumulated noise per output (one draw per *panel*) is unchanged.
    n_buses: int = 1
    # yield/failure model: buses (by physical index < n_buses) whose whole
    # modulator→bank→BPD chain is dead.  The GeMM compiler reroutes panels
    # onto the surviving buses — schedules lengthen (``n_bank_passes``
    # counts alive buses) but training keeps running; per-ring drift/cal
    # state keeps the full physical (n_buses, rows, cols) shape.
    failed_buses: tuple = ()
    noise_std: float = 0.0  # per-bank-pass Gaussian σ (0 = ideal hardware)
    noise_convention: str = "absolute"  # absolute | fullscale
    weight_bits: int | None = None  # fake-quant of inscribed MRR weights
    input_bits: int | None = None  # fake-quant of modulator amplitudes (DAC)
    f_s: float = 10e9  # operational rate (Hz), DAC-limited per the paper
    enabled: bool = True
    # device-level description for the "emu" backend (repro.hardware):
    # Lorentzian rings, crosstalk, drift, calibration.  None = the abstract
    # σ-per-MAC model only; the ref/pallas backends ignore it either way.
    mrr: MRRConfig | None = None

    @property
    def effective_bits(self) -> float:
        """log2(2/σ) — exact inverse of ``resolution_to_sigma``."""
        return sigma_to_resolution(self.noise_std)


# Paper-measured hardware presets (Figs. 3c, 5a).  The emu_* presets pair
# the measured per-pass σ with a device-level MRRConfig for the "emu"
# backend: emu_ideal is the nonideality-free bank (backend-equivalence
# baseline); emu_offchip / emu_onchip add realistic heater DACs, output
# ADCs, thermal crosstalk, and resonance drift (pair with
# ``TrainerConfig.recalibrate_every`` for in-situ calibration).
PRESETS: dict[str, PhotonicConfig] = {
    "ideal": PhotonicConfig(noise_std=0.0),
    "single_mrr": PhotonicConfig(noise_std=0.019),
    "offchip_bpd": PhotonicConfig(noise_std=0.098),
    "onchip_bpd": PhotonicConfig(noise_std=0.202),
    "digital": PhotonicConfig(enabled=False),
    "emu_ideal": PhotonicConfig(noise_std=0.0, mrr=MRRConfig.ideal()),
    "emu_offchip": PhotonicConfig(noise_std=0.098, mrr=MRRConfig(adc_bits=10)),
    "emu_onchip": PhotonicConfig(noise_std=0.202, mrr=MRRConfig(adc_bits=8)),
}


def preset(name: str) -> PhotonicConfig:
    return PRESETS[name]


def resolution_to_sigma(bits: float) -> float:
    """Effective resolution (bits) -> full-scale noise σ = 2^(1-bits)."""
    return 2.0 ** (1.0 - bits)


def sigma_to_resolution(sigma: float) -> float:
    """Full-scale noise σ -> effective bits = log2(2/σ), computed as
    1 - log2(σ) so the pair round-trips to float precision (the naive
    ``log2(2/σ)`` adds a division rounding; tests/test_photonics.py
    property-tests the inverse both ways)."""
    return 1.0 - math.log2(sigma) if sigma > 0 else float("inf")


def fake_quant(x, bits: int | None, amax=None):
    """Symmetric fake quantisation to ``bits`` over [-amax, amax].

    ``bits=1`` clamps to ternary/sign semantics ({-amax, 0, +amax}, the
    same grid as ``bits=2``): the naive symmetric formula has zero levels
    at one bit and used to return NaN."""
    if bits is None:
        return x
    if amax is None:
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    levels = max(2 ** (bits - 1) - 1, 1)
    scaled = jnp.clip(x / amax, -1.0, 1.0) * levels
    return jnp.round(scaled) / levels * amax


def n_contraction_panels(k_dim: int, cfg: PhotonicConfig) -> int:
    """Bank-sized panels along the contraction dim (GeMM compiler
    N-tiling) — the number of partial products *accumulated* per output,
    i.e. the noise-relevant count, independent of how many buses execute
    them in parallel."""
    return max(1, math.ceil(k_dim / cfg.bank_cols))


def active_buses(cfg: PhotonicConfig) -> int:
    """Buses actually carrying panels: the physical count minus the failed
    ones (``cfg.failed_buses``).  A chip with every bus dead cannot run."""
    n = max(cfg.n_buses, 1)
    failed = {b for b in cfg.failed_buses if 0 <= b < n}
    alive = n - len(failed)
    if alive < 1:
        raise ValueError(
            f"all {n} buses failed ({sorted(failed)}): no path through the chip")
    return alive


def alive_bus_indices(cfg: PhotonicConfig) -> tuple:
    """Physical indices of the surviving buses, in order — the panel
    scheduler's logical-bus → physical-bank map."""
    n = max(cfg.n_buses, 1)
    failed = {b for b in cfg.failed_buses if 0 <= b < n}
    return tuple(b for b in range(n) if b not in failed)


def n_bank_passes(k_dim: int, cfg: PhotonicConfig) -> int:
    """Operational cycles along the contraction dim: the surviving
    parallel banks each take one panel per cycle, so the schedule length
    is ⌈panels / active_buses⌉ (== panels on a single bus)."""
    return math.ceil(n_contraction_panels(k_dim, cfg) / active_buses(cfg))


def gemm_cycles(m: int, k: int, cfg: PhotonicConfig) -> int:
    """Total operational cycles for an (m×k)·(k,) matvec on the bank —
    the GeMM compiler's schedule length (paper §3), contraction panels
    bus-parallel per ``cfg.n_buses``."""
    return max(1, math.ceil(m / cfg.bank_rows)) * n_bank_passes(k, cfg)


def noise_sigma_total(k_dim: int, s_a, s_b, cfg: PhotonicConfig):
    """Std of the accumulated output noise for a length-k inner product,
    in natural (unnormalised) units.  Every contraction panel contributes
    one BPD read regardless of which bus ran it, so this counts panels,
    not bus-parallel cycles."""
    passes = n_contraction_panels(k_dim, cfg)
    if cfg.noise_convention == "absolute":
        per_pass = cfg.noise_std * s_a * s_b
    elif cfg.noise_convention == "fullscale":
        per_pass = cfg.noise_std * cfg.bank_cols * s_a * s_b
    else:
        raise ValueError(cfg.noise_convention)
    return per_pass * math.sqrt(passes)


def normalise_operands(a, b, cfg: PhotonicConfig):
    """Encode operands into the photonic [-1, 1] range: per-tensor amplitude
    normalisation followed by the DAC/weight fake-quant.  Shared by the
    reference path and the Pallas wrapper (kernels/ops.py) so both see
    identical encoding semantics.  -> (a_n, b_n, s_a, s_b)."""
    s_a = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(a)), 1e-12))
    s_b = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(b)), 1e-12))
    a_n = fake_quant(a / s_a, cfg.input_bits, 1.0)
    b_n = fake_quant(b / s_b, cfg.weight_bits, 1.0)
    return a_n, b_n, s_a, s_b


def photonic_matmul(a, b, cfg: PhotonicConfig, key=None):
    """Noisy C = A @ Bᵀ  (the weight-bank product).  Pure-JAX reference path.

    a: (..., T, K) — e.g. the error vectors (amplitude-encoded inputs)
    b: (M, K)      — the inscribed weight matrix panel (B(k) rows)
    Returns (..., T, M).
    """
    if not cfg.enabled:
        return jnp.einsum("...tk,mk->...tm", a, b)

    a_n, b_n, s_a, s_b = normalise_operands(a, b, cfg)
    out = jnp.einsum("...tk,mk->...tm", a_n, b_n)
    if cfg.noise_std > 0.0:
        if key is None:
            raise ValueError("noise_std > 0 requires a PRNG key")
        sigma = noise_sigma_total(a.shape[-1], 1.0, 1.0, cfg)  # normalised units
        noise = jax.random.normal(prng.consume(key), out.shape,
                                  dtype=out.dtype)
        if out.ndim == 2:
            noise = annotate(noise, "delta_tm")
            out = annotate(out, "delta_tm")
        out = out + sigma * noise
    return check_finite(out * (s_a * s_b), "photonic_matmul output")


# ---------------------------------------------------------------------------
# Execution backends
# ---------------------------------------------------------------------------
# A PhotonicBackend is *how* the weight-bank product is executed (pure-JAX
# einsum vs the Pallas TPU kernel); PhotonicConfig is *what* hardware is
# being modelled.  Backends are registered by name so new execution paths
# (e.g. an interferometer-mesh simulator, a real-hardware RPC bridge) are a
# registration, not an edit of every call site.


class PhotonicBackend:
    """Executes C = A @ Bᵀ (+ bank noise) with a:(T,K), b:(M,K)."""

    name = "base"
    # True when the backend consumes carried hardware state (drift /
    # calibration residuals): the Trainer then creates, advances, and
    # threads a per-ring state pytree through fit (see repro.hardware).
    stateful_hardware = False
    # False when the product runs in a Pallas kernel: XLA cannot partition
    # one, so under a mesh ``photonic_project`` runs it per shard
    partitionable = True

    def matmul(self, a, b, cfg: PhotonicConfig, key=None):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ReferenceBackend(PhotonicBackend):
    """Pure-JAX path: total accumulated noise drawn once per output."""

    name: str = "ref"

    def matmul(self, a, b, cfg, key=None):
        return photonic_matmul(a, b, cfg, key=key)


@dataclasses.dataclass(frozen=True)
class PallasBackend(PhotonicBackend):
    """MXU-tiled Pallas kernel (kernels/ops.py): per-block noise with the
    statistically identical variance.  ``interpret=True`` runs the kernel in
    the Pallas interpreter (CPU-validatable)."""

    name: str = "pallas"
    interpret: bool = False
    partitionable = False

    def matmul(self, a, b, cfg, key=None):
        from repro.kernels import ops as kops  # lazy: kernels import us

        return kops.photonic_matmul(a, b, cfg, key=key, interpret=self.interpret)


@dataclasses.dataclass(frozen=True)
class EmulatedMRRBackend(PhotonicBackend):
    """Device-level MRR bank emulation (repro.hardware.channel): Lorentzian
    ring transfer, heater inscription + DAC, thermal crosstalk, BPD
    shot/read noise, per-pass ADC — and, under the Trainer, stateful
    resonance drift with in-situ recalibration.  ``cfg.mrr`` describes the
    device (None falls back to ``MRRConfig()`` defaults).

    ``emu_kernel`` picks the execution path ("auto" | "ref" | "pallas" |
    "xla"): "ref" is the unfused einsum chain, "pallas"/"xla" run the
    fused panel loop of ``kernels.emu_matmul`` in one kernel per GEMM.
    "auto" consults ``REPRO_EMU_KERNEL`` and then the platform default
    (fused Pallas on TPU, ref elsewhere)."""

    name: str = "emu"
    stateful_hardware = True
    partitionable = False
    emu_kernel: str = "auto"

    def matmul(self, a, b, cfg, key=None):
        from repro.hardware import channel  # lazy: hardware imports us

        return channel.emulated_matmul(a, b, cfg, key=key, kernel=self.emu_kernel)


BACKENDS: dict[str, PhotonicBackend] = {}


def register_backend(backend: PhotonicBackend) -> PhotonicBackend:
    BACKENDS[backend.name] = backend
    return backend


register_backend(ReferenceBackend())
register_backend(PallasBackend())
register_backend(EmulatedMRRBackend())


def get_backend(spec: str | PhotonicBackend = "auto") -> PhotonicBackend:
    """Resolve a backend: an instance passes through; "auto" picks the
    Pallas kernel on TPU and the reference path elsewhere."""
    if isinstance(spec, PhotonicBackend):
        return spec
    if spec == "auto":
        spec = "pallas" if jax.default_backend() == "tpu" else "ref"
    if spec not in BACKENDS:
        raise KeyError(
            f"unknown photonic backend {spec!r}; registered: {sorted(BACKENDS)}")
    return BACKENDS[spec]


def photonic_project(e, b, cfg: PhotonicConfig, key=None, *,
                     backend: str | PhotonicBackend = "auto"):
    """DFA projection  δ = e·Bᵀ  through a registered backend.
    e: (..., d_tap), b: (d_out, d_tap).

    Under an active mesh (``sharding.use_mesh``) a backend whose product
    is a Pallas kernel runs per device on its rows of ``e``: the SPMD
    partitioner cannot split a kernel call (see ``_per_shard_matmul``)."""
    lead = e.shape[:-1]
    e2 = e.reshape(-1, e.shape[-1])
    be = get_backend(backend)
    mesh = current_mesh()
    if mesh is None or be.partitionable:
        out = be.matmul(e2, b, cfg, key=key)
    else:
        out = _per_shard_matmul(be, mesh, e2, b, cfg, key)
    return out.reshape(*lead, b.shape[0])


def _per_shard_matmul(backend: PhotonicBackend, mesh, a, b, cfg, key):
    """``backend.matmul`` inside ``shard_map`` over the mesh's batch axes.

    Each device projects its own rows of ``a`` through a replicated
    ``b``, as a data-parallel replica with a bank of its own would: the
    amplitude encoding is per shard, and the noise key is folded with the
    shard's index so that shards draw independent noise.  Carried
    hardware state (``drift.use_state``) enters replicated.  Rows that do
    not divide over the devices stay replicated and every device computes
    the whole product."""
    from repro.hardware import drift  # lazy: hardware imports us

    axes = batch_axes(mesh)
    n_shards = math.prod(mesh.shape[ax] for ax in axes)
    split = a.shape[0] % n_shards == 0
    rows = P(axes) if split else P()
    ops, specs = {"a": a, "b": b}, {"a": rows, "b": P()}
    if key is not None:
        ops["key"], specs["key"] = key, P()
    state = drift.active_state() if backend.stateful_hardware else None
    if state is not None:
        ops["state"], specs["state"] = state, P()

    def shard(ops):
        k = ops.get("key")
        if k is not None and split:
            k = jax.random.fold_in(k, jax.lax.axis_index(axes))
        ctx = (drift.use_state(ops["state"]) if "state" in ops
               else contextlib.nullcontext())
        with ctx:
            return backend.matmul(ops["a"], ops["b"], cfg, key=k)

    return jax.shard_map(shard, mesh=mesh, in_specs=(specs,), out_specs=rows,
                         check_vma=False)(ops)


# ---------------------------------------------------------------------------
# Forward-execution context (photonic inference)
# ---------------------------------------------------------------------------
# Training runs only the DFA feedback projections on the photonic banks;
# inference (repro.serve) runs the *forward* weight matrices through them.
# Rather than thread (cfg, backend, key) through every Linear/Attention
# call signature, the serve engine pushes a ForwardExecution context while
# tracing its jitted step — the same pattern as ``hardware.drift.use_state``
# — and ``forward_matmul`` below is the single seam every weight-stationary
# projection in nn/ and models/ calls.  Outside any context (or with
# ``cfg.enabled`` False) it is literally ``x @ w``: the training and
# digital-serving paths are bit-identical to before the seam existed.

_FORWARD: list = []


class ForwardExecution:
    """One photonic forward pass: config + backend + a PRNG stream that
    hands each routed matmul its own fold_in'd key (trace-order counter —
    deterministic under jit because tracing order is)."""

    def __init__(self, cfg: PhotonicConfig, backend, key=None):
        self.cfg = cfg
        self.backend = get_backend(backend)
        self.key = key
        self.calls = 0

    def next_key(self):
        if self.key is None:
            return None
        self.calls += 1
        return jax.random.fold_in(self.key, self.calls)


@contextlib.contextmanager
def forward_execution(cfg: PhotonicConfig, backend="ref", key=None):
    """Route every ``forward_matmul`` in the dynamic extent through
    ``backend`` under ``cfg``.  Enter *inside* the traced function so the
    key/state tracers belong to the consuming trace (cf. drift.use_state)."""
    ctx = ForwardExecution(cfg, backend, key)
    _FORWARD.append(ctx)
    try:
        yield ctx
    finally:
        _FORWARD.pop()


def active_forward() -> ForwardExecution | None:
    return _FORWARD[-1] if _FORWARD else None


def forward_matmul(x, w):
    """``x @ w`` with x: (..., K), w: (K, M) — THE forward projection seam.

    Digital (no active context / ``enabled=False``): exact ``x @ w``.
    Photonic: flatten leading dims to a (T, K) stream and run the weight
    bank product through the context's backend — the emu backend then
    prices in inscription error, quantisation, crosstalk, and any active
    drift state.  Biases, norms, and activations stay electronic (they are
    TIA-side ops, not bank products)."""
    ctx = active_forward()
    if ctx is None or not ctx.cfg.enabled:
        return x @ w
    lead = x.shape[:-1]
    a = x.reshape(-1, x.shape[-1])
    out = ctx.backend.matmul(a, w.T, ctx.cfg, key=ctx.next_key())
    return out.reshape(*lead, w.shape[-1]).astype(jnp.result_type(x, w))
