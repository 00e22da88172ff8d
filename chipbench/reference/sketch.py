"""Plain reference of a BlockPerm-SJLT sketch, ``Y = S A``, written from
the sketch's definition (FlashSketch, arXiv:2602.06071, §4 and App. D) and
importing nothing of the program.

The draw is fixed by the deployment's configuration: the seed, the block
grid (M blocks of Br output rows and Bc input rows), the degree κ and the
nonzeros s per block column.

* Wiring: output block g reads input blocks f^ℓ(g), ℓ = 1..κ, where
  f(x) = (a·x + b) mod M and (a, b) come from hashes of the seed.
* Inside block (g, h) input row u has s nonzeros, one per chunk of Br/s
  rows: nonzero i sits at row i·Br/s + H mod (Br/s) with sign from bit 31
  of H = hash(seed, g, h, u, i).
* Every nonzero has magnitude 1/√(κ·s).

The hash is a murmur3 finalizer folded over the words, as the paper's
App. D describes; it is written out here in numpy.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_MASK = 0xFFFFFFFF
_C1, _C2, _GAMMA = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def _fmix(x):
    x = np.asarray(x, np.uint64) & _MASK
    x ^= x >> 16
    x = (x * _C1) & _MASK
    x ^= x >> 13
    x = (x * _C2) & _MASK
    x ^= x >> 16
    return x


def hash_words(*words) -> np.ndarray:
    """uint32 hash of a sequence of words (scalars or broadcastable
    arrays)."""
    h = _fmix(np.asarray(words[0], np.uint64) + _GAMMA)
    for w in words[1:]:
        v = (_fmix(np.asarray(w, np.uint64) + _GAMMA) + _GAMMA) & _MASK
        h = _fmix(h ^ ((v + ((h << 6) & _MASK) + (h >> 2)) & _MASK))
    return h.astype(np.uint32)


def wiring(seed: int, M: int, kappa: int) -> np.ndarray:
    """(κ, M): the input block of each output block, per level ℓ."""
    h1 = int(hash_words(seed, 0xA11CE))
    h2 = int(hash_words(seed, 0xB0B))
    if M <= 2:
        a, b = 1, 1 % M
    else:
        a = (4 * (h1 % (M // 4)) + 1) % M
        if a == 1 and M >= 8:
            a = 5
        b = (2 * (h2 % (M // 2)) + 1) % M
    x = np.arange(M, dtype=np.int64)
    out = np.empty((kappa, M), np.int64)
    for ell in range(kappa):
        x = (a * x + b) % M
        out[ell] = x
    return out


def phi(sk: dict, h_of_g: np.ndarray) -> np.ndarray:
    """(M, Br, Bc) blocks Φ(g, h_of_g[g]) with entries ±1 and 0."""
    grid = sk["grid"]
    M, Br, Bc, s = grid["M"], grid["Br"], grid["Bc"], sk["s"]
    chunk = Br // s
    g = np.arange(M)[:, None]
    u = np.arange(Bc)[None, :]
    out = np.zeros((M, Br, Bc), np.float32)
    gi, ui = np.meshgrid(np.arange(M), np.arange(Bc), indexing="ij")
    for i in range(s):
        hsh = hash_words(sk["seed"], g, h_of_g[:, None], u, i)
        rows = i * chunk + (hsh % chunk).astype(np.int64)
        signs = np.where((hsh >> 31) & 1, -1.0, 1.0).astype(np.float32)
        out[gi, rows, ui] += signs
    return out


def check_grid(sk: dict) -> None:
    grid = sk["grid"]
    M, Br, Bc = grid["M"], grid["Br"], grid["Bc"]
    if M * Br != sk["k"] or M * Bc < sk["d"] or Br % sk["s"] or M < sk[
            "kappa"]:
        raise ValueError(f"grid {grid} does not realise {sk}")


def apply(sk: dict, A, precision: str = "float32"):
    """``Y = S A`` for a (d, n) operand, at ``precision``: ``float32``
    (fp32 operands and products, HIGHEST) or ``bfloat16`` (operand and
    entries rounded to bf16, one MXU pass, fp32 accumulation) — the latter
    is the control that the check must fail."""
    check_grid(sk)
    grid = sk["grid"]
    M, Br, Bc = grid["M"], grid["Br"], grid["Bc"]
    d, n = A.shape
    scale = 1.0 / math.sqrt(sk["kappa"] * sk["s"])
    low = precision == "bfloat16"
    dt = jnp.bfloat16 if low else jnp.float32
    prec = jax.lax.Precision.DEFAULT if low else jax.lax.Precision.HIGHEST
    blocks = jnp.pad(jnp.asarray(A, jnp.float32).astype(dt),
                     ((0, M * Bc - d), (0, 0))).reshape(M, Bc, n)
    Y = jnp.zeros((M, Br, n), jnp.float32)
    for h_of_g in wiring(sk["seed"], M, sk["kappa"]):
        P = jnp.asarray(phi(sk, h_of_g) * scale).astype(dt)
        Y = Y + jnp.einsum("gbc,gcn->gbn", P, blocks[h_of_g],
                           precision=prec,
                           preferred_element_type=jnp.float32)
    return Y.reshape(M * Br, n)


def rel_err(got, want) -> float:
    """max |got − want| / max |want|, in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
