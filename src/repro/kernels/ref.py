"""Pure-jnp reference oracles for the FlashSketch / FlashBlockRow kernels.

These are the ground-truth semantics: the Pallas kernels in
``flashsketch.py`` / ``blockrow.py`` must match them bit-for-bit in the hash
stream and to float tolerance in the output (asserted in tests).

Shapes follow the paper: ``A ∈ R^{d×n}``, ``S ∈ R^{k×d}``, ``Y = S A ∈ R^{k×n}``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, wiring
from repro.core.blockperm import BlockPermPlan, global_rows_signs


# Column-block width of the oracles' contractions.
_COL_BLOCK = 128


def block_einsum(spec: str, phi: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``einsum(spec, phi, x)`` at fp32 HIGHEST, over the column axis ``n``
    of ``x`` in fixed 128-wide blocks.  The CPU backend's matmul picks its
    blocking from the shape, so without this a column's result depends on
    how many columns share the call; with it, every column goes through
    an identically shaped contraction — the oracle is column-independent,
    as the kernels are.  ``spec`` names the column axis ``n``."""
    n = x.shape[-1]
    nb = -(-n // _COL_BLOCK)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * _COL_BLOCK - n)])
    x = x.reshape(*x.shape[:-1], nb, _COL_BLOCK)
    y = jnp.einsum(spec.replace("n", "jw"), phi, x,
                   precision=jax.lax.Precision.HIGHEST)
    return y.reshape(*y.shape[:-2], nb * _COL_BLOCK)[..., :n]


def pad_input(plan: BlockPermPlan, A: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad A from (d, n) to (d_pad, n)."""
    d, _ = A.shape
    if d == plan.d_pad:
        return A
    return jnp.pad(A, ((0, plan.d_pad - d), (0, 0)))


def _phi_all_blocks(plan: BlockPermPlan, h_of_g: jnp.ndarray) -> jnp.ndarray:
    """Φ for all output blocks at once: (M, Br, Bc), entries ±1/0 (unscaled).

    ``h_of_g``: (M,) int32, the input block feeding each output block for one
    permutation level ℓ.
    """
    g = jnp.arange(plan.M, dtype=jnp.int32)[:, None]      # (M, 1)
    u = jnp.arange(plan.Bc, dtype=jnp.int32)[None, :]     # (1, Bc)
    r_iota = jnp.arange(plan.Br, dtype=jnp.int32)         # (Br,)
    phi = jnp.zeros((plan.M, plan.Br, plan.Bc), jnp.float32)
    chunk = plan.chunk
    for i in range(plan.s):
        hsh = hashing.hash_words(
            np.uint32(plan.seed),
            g.astype(jnp.uint32),
            h_of_g[:, None].astype(jnp.uint32),
            u.astype(jnp.uint32),
            np.uint32(i),
        )                                                  # (M, Bc)
        rows = i * chunk + hashing.hash_mod(hsh, chunk)    # (M, Bc)
        signs = hashing.hash_to_unit_sign(hsh)             # (M, Bc)
        onehot = (r_iota[None, :, None] == rows[:, None, :]).astype(jnp.float32)
        phi = phi + onehot * signs[:, None, :]
    return phi


def _global_fwd_ref(plan: BlockPermPlan, A: jnp.ndarray) -> jnp.ndarray:
    """Y = S A for a GLOBAL family (countsketch/graph): scatter-add of each
    padded input row to its s hashed global output rows."""
    Ap = pad_input(plan, A).astype(jnp.float32)
    u = jnp.arange(plan.d_pad, dtype=jnp.int32)
    Y = jnp.zeros((plan.k_pad, Ap.shape[1]), jnp.float32)
    for i in range(plan.s):
        rows, signs = global_rows_signs(plan, u, i)
        Y = Y.at[rows].add(signs[:, None] * Ap)
    return Y[: plan.k] * plan.scale


def _global_transpose_ref(plan: BlockPermPlan, Y: jnp.ndarray) -> jnp.ndarray:
    """X = Sᵀ Y for a GLOBAL family: each padded input row gathers its s
    hashed output rows back."""
    Yp = Y
    if Y.shape[0] != plan.k_pad:
        Yp = jnp.pad(Y, ((0, plan.k_pad - Y.shape[0]), (0, 0)))
    Yp = Yp.astype(jnp.float32)
    u = jnp.arange(plan.d_pad, dtype=jnp.int32)
    X = jnp.zeros((plan.d_pad, Yp.shape[1]), jnp.float32)
    for i in range(plan.s):
        rows, signs = global_rows_signs(plan, u, i)
        X = X + signs[:, None] * Yp[rows]
    return X[: plan.d] * plan.scale


def flashsketch_ref(plan: BlockPermPlan, A: jnp.ndarray) -> jnp.ndarray:
    """Y = S A for S ~ plan (BLOCKPERM-SJLT or a global family).
    A: (d, n) -> Y: (k, n)."""
    if plan.is_global:
        return _global_fwd_ref(plan, A)
    n = A.shape[1]
    Ap = pad_input(plan, A).astype(jnp.float32)
    A_blocks = Ap.reshape(plan.M, plan.Bc, n)
    pi = wiring.wiring_jnp(plan.seed, plan.M, plan.kappa)   # (κ, M)
    Y_blocks = jnp.zeros((plan.M, plan.Br, n), jnp.float32)
    for ell in range(plan.kappa):
        h_of_g = pi[ell]                                    # (M,)
        gathered = A_blocks[h_of_g]                         # (M, Bc, n)
        phi = _phi_all_blocks(plan, h_of_g)                 # (M, Br, Bc)
        Y_blocks = Y_blocks + block_einsum("gbc,gcn->gbn", phi, gathered)
    Y = Y_blocks.reshape(plan.k_pad, n) * plan.scale
    return Y[: plan.k]


def flashsketch_transpose_ref(plan: BlockPermPlan, Y: jnp.ndarray) -> jnp.ndarray:
    """X = Sᵀ Y.  Y: (k, n) -> X: (d, n).  (VJP of flashsketch_ref wrt A.)"""
    if plan.is_global:
        return _global_transpose_ref(plan, Y)
    n = Y.shape[1]
    Yp = Y
    if Y.shape[0] != plan.k_pad:
        Yp = jnp.pad(Y, ((0, plan.k_pad - Y.shape[0]), (0, 0)))
    Y_blocks = Yp.reshape(plan.M, plan.Br, n).astype(jnp.float32)
    pi = wiring.wiring_jnp(plan.seed, plan.M, plan.kappa)
    X_blocks = jnp.zeros((plan.M, plan.Bc, n), jnp.float32)
    for ell in range(plan.kappa):
        h_of_g = pi[ell]
        phi = _phi_all_blocks(plan, h_of_g)                 # (M, Br, Bc)
        contrib = block_einsum("gbc,gbn->gcn", phi, Y_blocks)  # (M, Bc, n)
        X_blocks = X_blocks.at[h_of_g].add(contrib)
    X = X_blocks.reshape(plan.d_pad, n) * plan.scale
    return X[: plan.d]


# ---------------------------------------------------------------------------
# FLASHBLOCKROW (paper App. C): fast-but-fragile gather variant.
# Wiring is iid block sampling per output block (collisions possible); the
# intra-block pattern has s nonzeros per *row* (not per column) => no
# column-regularity, no OSE guarantee. Extra √(d/k) scaling (Alg. 2).
# ---------------------------------------------------------------------------

def blockrow_wiring(plan: BlockPermPlan) -> jnp.ndarray:
    """(κ, M) iid input-block choices for FLASHBLOCKROW."""
    g = jnp.arange(plan.M, dtype=jnp.uint32)[None, :]
    ell = jnp.arange(plan.kappa, dtype=jnp.uint32)[:, None]
    hsh = hashing.hash_words(
        np.uint32(plan.seed), np.uint32(0xB10C), ell, g
    )
    return hashing.hash_mod(hsh, plan.M)                    # (κ, M) int32


def _phi_rows_all_blocks(plan: BlockPermPlan, h_of_g: jnp.ndarray) -> jnp.ndarray:
    """Per-row sampling pattern: (M, Br, Bc) with s ±1 entries per row."""
    g = jnp.arange(plan.M, dtype=jnp.int32)[:, None]        # (M, 1)
    r = jnp.arange(plan.Br, dtype=jnp.int32)[None, :]       # (1, Br)
    c_iota = jnp.arange(plan.Bc, dtype=jnp.int32)           # (Bc,)
    phi = jnp.zeros((plan.M, plan.Br, plan.Bc), jnp.float32)
    for t in range(plan.s):
        hsh = hashing.hash_words(
            np.uint32(plan.seed),
            np.uint32(0x5EED),
            g.astype(jnp.uint32),
            h_of_g[:, None].astype(jnp.uint32),
            r.astype(jnp.uint32),
            np.uint32(t),
        )                                                   # (M, Br)
        cols = hashing.hash_mod(hsh, plan.Bc)               # (M, Br)
        signs = hashing.hash_to_unit_sign(hsh)              # (M, Br)
        onehot = (c_iota[None, None, :] == cols[:, :, None]).astype(jnp.float32)
        phi = phi + onehot * signs[:, :, None]
    return phi


def blockrow_ref(plan: BlockPermPlan, A: jnp.ndarray) -> jnp.ndarray:
    """FLASHBLOCKROW forward: Y = S_row A with the Alg. 2 scaling."""
    n = A.shape[1]
    Ap = pad_input(plan, A).astype(jnp.float32)
    A_blocks = Ap.reshape(plan.M, plan.Bc, n)
    hh = blockrow_wiring(plan)                              # (κ, M)
    Y_blocks = jnp.zeros((plan.M, plan.Br, n), jnp.float32)
    for ell in range(plan.kappa):
        h_of_g = hh[ell]
        gathered = A_blocks[h_of_g]
        phi = _phi_rows_all_blocks(plan, h_of_g)
        Y_blocks = Y_blocks + block_einsum("gbc,gcn->gbn", phi, gathered)
    scale = plan.scale * math.sqrt(plan.d_pad / plan.k_pad)
    Y = Y_blocks.reshape(plan.k_pad, n) * scale
    return Y[: plan.k]
