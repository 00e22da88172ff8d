"""GraSS: per-example gradient → sparsify → sketch → feature cache →
attribution (paper §7.4 / App. E).  The sparsify→sketch step — the paper's
measured bottleneck — runs on the gather-fused batched FlashSketch path:
per-example gradients are produced in ``lax.scan`` chunks (vmapped inside
each chunk), and every chunk is sketched in ONE kernel launch that gathers
the sparsify mask's coordinates directly out of the stacked gradients — no
``grads[:, mask]`` intermediate, no per-example launches.  Any variant from
``repro.core.variants`` can be swapped in for the Pareto benchmarks
(families without a fused kernel fall back to a materializing gather).

Where the gradient comes from is a ``GradientSource``: the MLP's flattened
margin gradient (``MarginGradients``, the default) or a factorized
per-layer capture whose rows are already sparsified
(``attribution.moonlight.MoonlightGradients``).  The scan, the quarantine,
the sketch step and the program scopes are one body for every source.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.attribution import mlp as mlp_lib
from repro.core import hashing
from repro.core.variants import SketchBase, make_sketch
from repro.health import report as health_report
from repro.tracing import scope, scoped


@dataclasses.dataclass(frozen=True)
class GrassPipelineConfig:
    sparse_dim: int = 4096         # gradient sparsification target (App. E)
    sketch_dim: int = 1024         # k
    sketch_family: str = "blockperm"
    sketch_kwargs: tuple = ()      # extra (key, value) pairs
    seed: int = 0
    attribution: str = "dot"       # "dot" | "kernel" (TRAK preconditioned)
    lam_rel: float = 1.0           # kernel ridge relative to mean eigenvalue
    chunk: int = 64                # examples per scan step / fused launch
    fused: bool = True             # gather-fused sketch (False: materialize
                                   # grads[:, mask] — the pre-fusion path,
                                   # kept for A/B tests and benchmarks)


def _flat_grad_fn(params):
    """Per-example gradient of the margin output, flattened."""
    def gfn(p, x, y):
        g = jax.grad(lambda pp: mlp_lib.margin_output(pp, x[None], y[None])[0])(p)
        return jnp.concatenate([a.reshape(-1) for a in jax.tree.leaves(g)])
    return gfn


class GradientSource:
    """What ``GrassPipeline.featurize`` asks of a per-example gradient.

    ``dim`` is the number of coordinates the sketch sees per example;
    ``gathers`` says whether ``sketch_rows`` gathers them out of wider rows
    (the fused gather kernel) or sketches the rows as they are.
    """

    dim: int
    gathers: bool = False

    def rows(self, p, xb, yb):
        """``(rows (c, D), stats)`` for one chunk: each example's gradient
        row, and per-example statistics (a pytree of arrays with leading
        axis ``c``, summed over a call and handed to ``note``)."""
        raise NotImplementedError

    def sketch_rows(self, sketch: SketchBase, rows) -> jnp.ndarray:
        """``(c, k)`` features of a chunk's (quarantined) rows."""
        return sketch.apply(rows.T).T

    def note(self, stats) -> None:
        """Host side, after a call: the call's summed ``stats``."""


class MarginGradients(GradientSource):
    """The MLP's flattened per-example margin gradient (``mlp.py``): all
    ``d_total`` parameters, of which the sparsify mask keeps
    ``min(cfg.sparse_dim, d_total)``; the sketch gathers them in-kernel
    (``cfg.fused``) or from a materialized ``grads[:, mask]``."""

    def __init__(self, params, cfg: GrassPipelineConfig):
        self.d_total = sum(p.size for p in jax.tree.leaves(params))
        self.dim = min(cfg.sparse_dim, self.d_total)
        self.mask = sparsify_mask(self.d_total, self.dim, cfg.seed)
        self.gathers = cfg.fused
        self.grad_fn = _flat_grad_fn(params)

    def rows(self, p, xb, yb):
        return jax.vmap(lambda x, y: self.grad_fn(p, x, y))(xb, yb), ()

    def sketch_rows(self, sketch, grads):
        if self.gathers:
            return sketch.apply_gather(grads.T, self.mask).T
        return sketch.apply(grads[:, self.mask].T).T


def sparsify_mask(d_total: int, d_keep: int, seed: int) -> jnp.ndarray:
    """GraSS gradient sparsification: a fixed random coordinate subset.

    Selects the d_keep coordinates with the SMALLEST hash scores via
    ``lax.top_k`` on the bitwise complement — O(d log k) with no d-length
    sort buffer, and bitwise-identical to the historical full
    ``argsort(scores)[:d_keep]`` (uint32 complement reverses the order
    exactly; both break ties toward the lower index).
    """
    u = jnp.arange(d_total, dtype=jnp.uint32)
    scores = hashing.hash_words(np.uint32(seed), np.uint32(0x6A55), u)
    _, idx = jax.lax.top_k(~scores, d_keep)
    return jnp.sort(idx)


class GrassPipeline:
    """Feature-cache builder around the fused batched sketch.

    ``featurize`` runs the per-example gradients in ``cfg.chunk``-sized
    ``lax.scan`` steps (vmap inside the step), each chunk feeding one
    gather-fused batched sketch launch; the feature cache is assembled
    chunk by chunk.  With ``cfg.fused=False`` the same scan materializes
    ``grads[:, mask]`` before sketching (the seed behavior, bit-compatible
    features).

    Multi-device: pass ``mesh``/``shard_axis`` to BATCH-SHARD featurize
    over the chunk axis — every device scans its own chunks (params and
    mask replicated, no collective; examples are independent), so the
    feature cache builds P× wider per step.  Features are identical to the
    single-device run (chunks are computed by the same per-chunk launch
    either way).

    Health: a per-example gradient with any non-finite entry (a NaN-poisoned
    batch element, an overflowed activation) is QUARANTINED in-kernel —
    zeroed before the sketch, so one bad example contributes nothing instead
    of poisoning its whole chunk's feature block — and counted
    (``.quarantined``, plus the process-wide ``grass.quarantined`` health
    counter).  The mask is computed inside the jitted scan (a ``jnp.where``
    per chunk), so the guarded path costs one finiteness reduction per
    gradient row.

    Tracing: each chunk's three steps carry the program scopes
    ``repro.grass.grads``, ``repro.grass.quarantine`` and
    ``repro.grass.sketch`` (``repro.tracing``), so a device trace splits
    the scan's time by step; ``featurize`` runs under the host span
    ``repro.grass.featurize``.

    Source: ``source`` supplies each chunk's gradient rows and how they
    are sketched (``GradientSource``); the default is the MLP's margin
    gradient of ``params`` (``MarginGradients``), the one that
    ``cfg.sparse_dim`` and ``cfg.fused`` apply to.
    """

    def __init__(self, cfg: GrassPipelineConfig, params, mesh=None,
                 shard_axis: str = "data",
                 source: Optional[GradientSource] = None):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.quarantined = 0           # rows zeroed across all featurize calls
        self.source = source if source is not None else \
            MarginGradients(params, cfg)
        self.sketch: SketchBase = make_sketch(
            cfg.sketch_family, self.source.dim, cfg.sketch_dim, seed=cfg.seed,
            **dict(cfg.sketch_kwargs))
        src = self.source

        def featurize(p, xs, ys):
            b = xs.shape[0]
            c = max(1, min(cfg.chunk, b))
            n_chunks = -(-b // c)
            if mesh is not None:
                # batch-sharded: every device scans n_chunks/P chunks
                n_dev = mesh.shape[shard_axis]
                n_chunks = -(-n_chunks // n_dev) * n_dev
            pad = n_chunks * c - b
            if pad:
                # repeat the first example: gradients stay well-defined and
                # the padded features are sliced off below
                xs = jnp.concatenate([xs, jnp.broadcast_to(
                    xs[:1], (pad,) + xs.shape[1:])])
                ys = jnp.concatenate([ys, jnp.broadcast_to(
                    ys[:1], (pad,) + ys.shape[1:])])
            xc = xs.reshape((n_chunks, c) + xs.shape[1:])
            yc = ys.reshape((n_chunks, c) + ys.shape[1:])

            def chunk_feats(p_, xy):
                """One chunk: the source's gradient rows -> quarantine ->
                sketch.  The SAME body drives both branches, so sharded
                features cannot drift from single-device ones.  Non-finite
                gradient rows are zeroed (quarantined) before the sketch
                and flagged — jit-compatible (a where, not a branch)."""
                xb, yb = xy
                with scope("repro.grass.grads"):
                    grads, stats = src.rows(p_, xb, yb)
                with scope("repro.grass.quarantine"):
                    ok = jnp.all(jnp.isfinite(grads), axis=1)
                    grads = jnp.where(ok[:, None], grads, 0.0)
                with scope("repro.grass.sketch"):
                    feats = src.sketch_rows(self.sketch, grads)
                return feats, ~ok, stats    # (c, k), (c,), (c, ...) a chunk

            if mesh is None:
                _, (feats, bad, stats) = jax.lax.scan(
                    lambda car, xy: (car, chunk_feats(p, xy)), 0, (xc, yc))
            else:
                from jax.sharding import PartitionSpec as P

                def scan_local(p_, xcl, ycl):
                    _, fb = jax.lax.scan(
                        lambda car, xy: (car, chunk_feats(p_, xy)),
                        0, (xcl, ycl))
                    return fb

                feats, bad, stats = jax.shard_map(
                    scan_local, mesh=mesh,
                    in_specs=(P(), P(shard_axis), P(shard_axis)),
                    out_specs=P(shard_axis),
                    check_vma=False,
                )(p, xc, yc)
            # padded tail rows are sliced off BEFORE the bad-row count and
            # the stats' sum, so a padding copy of an example counts nothing
            stats = jax.tree.map(
                lambda a: a.reshape((n_chunks * c,) + a.shape[2:])[:b]
                .sum(axis=0), stats)
            return (feats.reshape(n_chunks * c, -1)[:b],
                    bad.reshape(n_chunks * c)[:b], stats)

        self._featurize = jax.jit(featurize)

    @scoped("repro.grass.featurize")
    def featurize(self, xs, ys) -> jnp.ndarray:
        """Sketched features for a batch; quarantines non-finite rows.

        Returns the ``(b, k)`` feature block.  Rows whose per-example
        gradient contained any non-finite entry come back as zeros and are
        added to ``.quarantined`` / the ``grass.quarantined`` counter.
        """
        feats, bad, stats = self._featurize(self.params, xs, ys)
        self._note(bad, stats)
        return feats

    def _note(self, bad, stats) -> None:
        self._note_quarantine(bad)
        self.source.note(stats)

    def _note_quarantine(self, bad) -> None:
        nbad = int(np.asarray(bad).sum())
        if nbad:
            self.quarantined += nbad
            health_report.record("grass.quarantined", n=nbad,
                                 detail=f"{nbad} non-finite gradient rows "
                                        f"zeroed before sketch")

    def health(self) -> health_report.HealthReport:
        """A ``HealthReport`` summarizing this pipeline's quarantine state."""
        rpt = health_report.HealthReport(op="featurize",
                                         quarantined=self.quarantined)
        if self.quarantined:
            rpt.add(health_report.GuardFinding(
                "finite", "grads", health_report.DEGRADED,
                value=float(self.quarantined),
                detail=f"{self.quarantined} gradient rows quarantined"))
        return rpt

    def sketch_lowering(self):
        """The ``kernels.lowering.Lowering`` record of one featurize-chunk
        sketch launch — how the sparsify→sketch step actually runs (fused
        gather or materialized, which kernel, which tile).  ``None`` for
        sketch families without a FlashSketch kernel (they run as plain
        XLA ops).  Inspect with ``.describe()`` or price it with
        ``repro.engine.cost_of``."""
        return self.sketch.lowering_for(max(1, self.cfg.chunk),
                                        gather=self.source.gathers)

    # ---------------------------------------------------------------- cache
    def build_cache(self, x_train, y_train, batch: int = 256) -> Tuple[jnp.ndarray, float]:
        """Feature cache Φ ∈ (n_train, k); returns (cache, sketch_seconds).

        Each ``batch`` slab runs one jitted scan whose per-chunk fused
        launches write the cache incrementally (chunk size ``cfg.chunk``).
        """
        feats = []
        t = 0.0
        for i in range(0, x_train.shape[0], batch):
            xb = x_train[i:i + batch]
            yb = y_train[i:i + batch]
            t0 = time.perf_counter()
            f, bad, stats = self._featurize(self.params, xb, yb)
            f.block_until_ready()
            t += time.perf_counter() - t0
            self._note(bad, stats)
            feats.append(f)
        return jnp.concatenate(feats, axis=0), t

    # ----------------------------------------------------------- attribution
    def attribute(self, cache: jnp.ndarray, x_test, y_test) -> np.ndarray:
        """τ(z)_i: sketched-gradient similarity.

        "dot":    τ = φ_z · φ_i           (GraSS default; robust at small n)
        "kernel": τ = φ_zᵀ (ΦᵀΦ + λI)⁻¹ φ_i  (TRAK preconditioning; λ set
                  relative to the mean kernel eigenvalue).
        """
        phi_z = self.featurize(x_test, y_test)                   # (nt, k)
        if self.cfg.attribution == "dot":
            tau = phi_z @ cache.T                                # (nt, n_train)
            return np.asarray(tau)
        k = cache.shape[1]
        K = cache.T @ cache
        lam = self.cfg.lam_rel * jnp.trace(K) / k
        sol = jnp.linalg.solve(K + lam * jnp.eye(k), phi_z.T)    # (k, nt)
        tau = cache @ sol                                        # (n_train, nt)
        return np.asarray(tau.T)                                 # (nt, n_train)


def run_grass_lds(
    pipe_cfg: GrassPipelineConfig,
    mlp_cfg: mlp_lib.MLPConfig,
    n_train: int = 512,
    n_test: int = 32,
    m_subsets: int = 20,
    alpha: float = 0.5,
    seed: int = 0,
) -> Dict[str, float]:
    """End-to-end GraSS + LDS evaluation (the paper Fig. 4 pipeline)."""
    from repro.attribution import lds as lds_lib

    x, y = mlp_lib.make_synthetic_mnist(n_train + n_test, mlp_cfg.d_in,
                                        mlp_cfg.n_classes, seed=seed)
    x_tr, y_tr = x[:n_train], y[:n_train]
    x_te, y_te = x[n_train:], y[n_train:]

    base = mlp_lib.train_mlp(mlp_cfg, x_tr, y_tr)
    pipe = GrassPipeline(pipe_cfg, base)
    cache, sketch_s = pipe.build_cache(x_tr, y_tr)
    tau = pipe.attribute(cache, x_te, y_te)

    masks = lds_lib.sample_subsets(n_train, m_subsets, alpha, seed)
    true_out = np.empty((m_subsets, n_test))
    for j in range(m_subsets):
        pj = mlp_lib.train_mlp(mlp_cfg, x_tr, y_tr,
                               key=jax.random.PRNGKey(1000 + j),
                               mask=masks[j])
        true_out[j] = np.asarray(mlp_lib.margin_output(pj, x_te, y_te))
    score = lds_lib.lds_score(true_out, tau, masks)
    return {
        "lds": score,
        "sketch_seconds": sketch_s,
        "sketch_family": pipe_cfg.sketch_family,
        "k": pipe_cfg.sketch_dim,
        "per_sample_us": 1e6 * sketch_s / n_train,
    }
