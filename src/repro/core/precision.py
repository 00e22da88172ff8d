"""Precision as a first-class lowering axis (ROADMAP item 3).

One frozen :class:`Precision` record answers every question the stack
used to re-derive from the ``plan.dtype`` string with scattered
``"bfloat16" ? 2 : 4`` ternaries: what dtype the operand streams from
HBM in, what the MXU contraction inputs are, what dtype accumulates,
how the stream cast rounds, how many bytes a streamed element costs the
roofline, and how far the health guards' isometry/OSE bands must widen
before a draw is blamed on the sketch rather than on the quantizer.

Registered policies (canonical name → record)::

    float32      fp32 stream,  fp32 MXU,    nearest    4 B/elem
    bfloat16     bf16 stream,  bf16 MXU,    nearest    2 B/elem
    fp8_e4m3     e4m3 stream,  bf16 MXU,    nearest    1 B/elem
    fp8_e5m2     e5m2 stream,  bf16 MXU,    nearest    1 B/elem
    fp8_e4m3_sr  e4m3 stream,  bf16 MXU,    stochastic 1 B/elem
    fp8_e5m2_sr  e5m2 stream,  bf16 MXU,    stochastic 1 B/elem

``"fp32"`` and ``"bf16"`` are accepted as aliases.  The canonical
spelling of the two legacy policies is kept as ``"float32"`` /
``"bfloat16"`` on purpose: ``plan.dtype`` (and therefore
``tune.cache_key`` and the golden lowering snapshot) stores the
canonical name, so tuner caches and snapshots saved before this module
existed keep resolving.

Accumulation is fp32 for every policy (the kernels pin
``preferred_element_type``); fp8 operands are upcast to bf16 *inside*
the kernel — exact, since every e4m3/e5m2 value is representable in
bf16 — so HBM pays 1 byte/elem while the MXU runs at its bf16 rate.

Stochastic rounding is **value-keyed**: the uniform draw deciding
whether ``x`` rounds up or down is a counter hash of ``(seed, tag,
bits(x))`` (``core.hashing``, the same splitmix/murmur mix the sketch
itself uses).  For a fixed seed the quantizer is a deterministic pure
function of the value — bit-identical regardless of array shape,
batching, gather order or which kernel streams it — while across seeds
``E[quantize(x)] ≈ x`` (unbiased), which is what makes SR the right
rounding for iterative refinement (Jeendgar/Flint/Anzt, PAPERS.md
arXiv 2606.20195).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing

# Hash domain tag separating the SR draws from the sketch's own hashes.
_SR_TAG = np.uint32(0xF80D)

# jnp dtypes by stream-dtype name — the ONLY place in the repo mapping a
# policy string to a jnp dtype / itemsize (grep-clean criterion, ISSUE 9).
_JNP = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
}
_ITEMSIZE = {"float32": 4, "bfloat16": 2,
             "float8_e4m3fn": 1, "float8_e5m2": 1}


@dataclasses.dataclass(frozen=True)
class Precision:
    """A named streaming-precision policy: stream/accumulate dtypes, the
    rounding mode of the HBM cast, and the guard tolerance bands the
    policy is entitled to.  Frozen and hashable — safe to hang off the
    (pytree-static) :class:`~repro.core.blockperm.BlockPermPlan`."""

    name: str                     # canonical registry name
    stream: str                   # dtype name the operand streams in
    accum: str = "float32"        # accumulation dtype (MXU preferred type)
    rounding: str = "nearest"     # "nearest" | "stochastic"
    # guard tolerance bands (health/guards.py defaults come FROM here)
    isometry_tol: float = 0.5     # healthy: ‖SA‖_F/‖A‖_F within 1 ± tol
    isometry_fail: float = 0.9    # failed: outside 1 ± fail
    ose_min_healthy: float = 0.5  # σ_min(SU) healthy floor
    ose_min_failed: float = 0.1   # σ_min(SU) failed floor
    exactness_atol: float = 5e-4  # kernel-vs-oracle comparison tolerance

    # -- dtype accessors ----------------------------------------------------
    @property
    def stream_dtype(self):
        """jnp dtype the operand is stored/streamed in (HBM side)."""
        return _JNP[self.stream]

    @property
    def accum_dtype(self):
        """jnp dtype of the MXU accumulator (``preferred_element_type``)."""
        return _JNP[self.accum]

    @property
    def compute_dtype(self):
        """jnp dtype of the MXU *inputs*: the in-kernel upcast target.

        fp8 operands are widened to bf16 before the contraction (exact —
        e4m3/e5m2 ⊂ bf16); fp32/bf16 streams feed the MXU directly."""
        return _JNP["bfloat16"] if self.is_fp8 else self.stream_dtype

    @property
    def itemsize(self) -> int:
        """Bytes per streamed element — the roofline's HBM charge."""
        return _ITEMSIZE[self.stream]

    @property
    def compute_itemsize(self) -> int:
        """Bytes per MXU input element (selects the modeled MXU rate)."""
        return 2 if self.is_fp8 else self.itemsize

    @property
    def is_fp8(self) -> bool:
        return self.stream.startswith("float8")

    @property
    def stochastic(self) -> bool:
        return self.rounding == "stochastic"

    def isometry_band(self) -> Dict[str, float]:
        """kwargs for :func:`repro.health.guards.isometry_guard`."""
        return {"tol": self.isometry_tol, "fail": self.isometry_fail}

    def ose_band(self) -> Dict[str, float]:
        """kwargs for :func:`repro.health.guards.ose_probe`."""
        return {"min_healthy": self.ose_min_healthy,
                "min_failed": self.ose_min_failed}


_FP8_BAND = dict(isometry_tol=0.6, isometry_fail=0.95,
                 ose_min_healthy=0.4, ose_min_failed=0.05,
                 exactness_atol=5e-3)

# Canonical registry. Insertion order = documentation order.
POLICIES: Dict[str, Precision] = {
    p.name: p for p in (
        Precision("float32", "float32", exactness_atol=1e-5),
        Precision("bfloat16", "bfloat16"),
        Precision("fp8_e4m3", "float8_e4m3fn", **_FP8_BAND),
        Precision("fp8_e5m2", "float8_e5m2", **_FP8_BAND),
        Precision("fp8_e4m3_sr", "float8_e4m3fn", rounding="stochastic",
                  **_FP8_BAND),
        Precision("fp8_e5m2_sr", "float8_e5m2", rounding="stochastic",
                  **_FP8_BAND),
    )
}

# Validated string shorthands (legacy spellings stay canonical, see module
# docstring; the short forms are conveniences for CLIs and configs).
ALIASES: Dict[str, str] = {"fp32": "float32", "bf16": "bfloat16"}


def names() -> Tuple[str, ...]:
    """All accepted policy spellings (canonical names + aliases)."""
    return tuple(POLICIES) + tuple(ALIASES)


def resolve(policy: Union[str, Precision]) -> Precision:
    """Policy name/alias (or an already-resolved record) → ``Precision``."""
    if isinstance(policy, Precision):
        return policy
    key = ALIASES.get(policy, policy)
    try:
        return POLICIES[key]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown precision policy {policy!r}; registered: "
            f"{', '.join(names())}") from None


def canonical(policy: Union[str, Precision]) -> str:
    """Canonical registry name for a policy/alias (validates)."""
    return resolve(policy).name


# ---------------------------------------------------------------------------
# Quantization: the streaming cast.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _finite_grid(stream: str) -> np.ndarray:
    """Sorted ascending array of every finite value of an 8-bit float.

    256 bit patterns → ≤ 253 distinct finite values; tiny enough to hold
    as a literal table, which sidesteps every next-representable
    bit-twiddling trap (sign-magnitude order, subnormals, e4m3's missing
    inf encoding)."""
    dt = np.dtype(_JNP[stream])
    vals = np.arange(256, dtype=np.uint8).view(dt).astype(np.float32)
    return np.unique(vals[np.isfinite(vals)])


def fp8_max(policy: Union[str, Precision]) -> float:
    """Largest finite value of an fp8 policy's stream dtype."""
    p = resolve(policy)
    if not p.is_fp8:
        raise ValueError(f"{p.name} is not an fp8 policy")
    return float(_finite_grid(p.stream)[-1])


def _uniform_from_bits(seed, x32: jnp.ndarray) -> jnp.ndarray:
    """Value-keyed U[0,1) draw: hash of (seed, tag, bitpattern of x)."""
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    h = hashing.hash_words(np.uint32(int(seed) & 0xFFFFFFFF), _SR_TAG, bits)
    return (h >> np.uint32(8)).astype(jnp.float32) * np.float32(1.0 / (1 << 24))


def quantize_stream(x: jnp.ndarray, policy: Union[str, Precision],
                    *, seed: int = 0) -> jnp.ndarray:
    """Cast ``x`` to the policy's streaming dtype — THE streaming cast.

    ``nearest`` policies round to nearest-even (clamped to the finite
    range first: overflow must saturate, not produce e4m3's nan).
    ``stochastic`` policies round each value up with probability equal
    to its fractional position between its two fp8 neighbors, using the
    value-keyed seeded draw described in the module docstring: exact
    passthrough for representable values, unbiased over seeds,
    bit-deterministic for a fixed seed.
    """
    p = resolve(policy)
    x32 = x.astype(jnp.float32)
    if not p.is_fp8:
        return x.astype(p.stream_dtype)
    grid = jnp.asarray(_finite_grid(p.stream))
    x32 = jnp.clip(x32, grid[0], grid[-1])
    if not p.stochastic:
        return x32.astype(p.stream_dtype)
    lo_idx = jnp.clip(jnp.searchsorted(grid, x32, side="right") - 1,
                      0, grid.shape[0] - 2)
    lo = grid[lo_idx]
    hi = grid[lo_idx + 1]
    frac = jnp.where(hi > lo, (x32 - lo) / (hi - lo), 0.0)
    up = _uniform_from_bits(seed, x32) < frac
    return jnp.where(up, hi, lo).astype(p.stream_dtype)


def fp32_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at full fp32 precision on every backend.

    The TPU's default fp32 matmul rounds both inputs to bf16 (8 mantissa
    bits): too coarse for an fp32 LSQR residual of 1e-6, and for an fp32
    reference to check an fp32 kernel against.  The solvers' matvecs and Gram
    products go through here; the kernels' own contractions pin the same
    precision (``kernels.flashsketch._dot``)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def emulate_stream(x: jnp.ndarray, policy: Union[str, Precision],
                   *, seed: int = 0) -> jnp.ndarray:
    """Round ``x`` through the streaming dtype, returned as fp32.

    What the XLA oracle / fp32 v1 kernels apply so their results carry
    the SAME stream quantization as the v2 kernels (which receive the
    ``quantize_stream`` output directly)."""
    p = resolve(policy)
    if p.name == "float32":
        return x.astype(jnp.float32)
    return quantize_stream(x, p, seed=seed).astype(jnp.float32)
