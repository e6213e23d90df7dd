"""Joint embedding + quantizer training (paper §3.1-3.3) — the
trainer-layer home of what used to be ``core/train.py`` (now a thin
re-export, mirroring how PR 2 folded ``core/search.py`` into the index
layer).

One trainer covers ICQ and the ablation/baseline modes by switching the
active loss terms (paper eq. 3 augmented):

    mode="icq":  L^E + L^C + gamma1 L^P + gamma2 L^ICQ (+ CQ penalty)
    mode="cq":   L^E + L^C + CQ penalty          (SQ = linear embed + cq)
    mode="pq":   L^E + L^C with codebooks hard-projected onto contiguous
                 subspaces after every step (PQ/PQN-style)

Gradient flow notes:
- Lambda is the *online* variance estimate (eq. 9, core.variance); its
  value comes from the running state but its gradient flows through the
  current batch's sample variance (straight-through running stats), so
  L^P shapes the embedding W as intended.
- xi is hard for search but L^ICQ uses the prior's soft responsibilities
  (minor-mode posterior) so the interleaving penalty stays differentiable
  in Theta.
- L^C uses straight-through soft assignments (core.encode.st_decode);
  codebooks get dense gradients, embeddings see the hard reconstruction.

The step is pure JAX; drivers compile it either per-batch (host loop)
or as a whole epoch (``trainer.epoch`` — ``lax.scan`` over
device-resident batches with donated state, DESIGN.md §9).  With
``axis_name`` set the step is data-parallel-ready: gradients are
pmean'd over the mesh axis and the Lambda update consumes *global*
batch moments, so every shard applies the identical state transition.
Encode-side ICM re-encoding happens at export time (``finalize``)
through the tiled encoding engine.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codebooks as cb
from repro.core import embed as embed_mod
from repro.core import icq as icq_mod
from repro.core import losses
from repro.core import prior as prior_mod
from repro.core import variance
from repro.trainer.base import ICQModel
from repro.train.optimizer import AdamW


def _pq_support_mask(K: int, d: int):
    """(K,d) 0/1 contiguous-subspace masks (PQ)."""
    assert d % K == 0
    sub = d // K
    m = jnp.zeros((K, d))
    for k in range(K):
        m = m.at[k, k * sub:(k + 1) * sub].set(1.0)
    return m


def fit_unit(emb) -> float:
    """The scale the fit works in: the power of two that brings the RMS
    entry of ``emb`` into [16, 32), 1.0 for an all-zero sample.

    The fit's absolute constants (the AdamW step ``lr``, the soft
    assignment's ``tau``, the prior's 1e-2 floors, the gradient clip)
    act in the embedding's own units, so on rows of RMS entry 0.1 one step moves a
    codeword by a large share of its length where on rows of RMS 20 it
    barely moves it.  Training on ``emb * unit`` and dividing the unit
    back out at export makes the fit the same, bit for bit, for any
    power-of-two rescale of the rows; as a power of two the rescale is
    exact, so rows already in range fit exactly as without it."""
    rms = float(np.sqrt(np.mean(np.square(np.asarray(emb, np.float64)))))
    if not (np.isfinite(rms) and rms > 0.0):
        return 1.0
    _, e = np.frexp(rms)                  # rms = f * 2**e, f in [0.5, 1)
    return float(2.0 ** (5 - e))


def _in_unit(emb, unit: float):
    return emb if unit == 1.0 else emb * unit


def _icq_supports(lam, theta, icq_cfg):
    """(K, d) 0/1 supports of the ICQ warm start: the psi dimensions the
    prior picks from the sample's variances (as ``finalize`` picks them)
    split in runs over the ``num_fast`` fast codebooks, the others over
    the slow ones.  Each codebook then owns its own dimensions, as in
    PQ: the export's projection cuts nothing from it, and the encoder's
    independent warm start is already the best code.  Full-width
    residual codebooks lose most of their fit when cut to their side of
    psi (a fifth of 64-bit PQ's recall on SIFT- and Deep-shaped rows)."""
    K, nf = icq_cfg.num_codebooks, icq_cfg.num_fast
    xi = np.asarray(icq_mod.compute_xi(lam, theta, icq_cfg,
                                       min_dims=max(1, icq_cfg.d // K)))
    mask = np.zeros((K, xi.shape[-1]), np.float32)
    for books, dims in ((range(nf), np.flatnonzero(xi)),
                        (range(nf, K), np.flatnonzero(~xi))):
        if len(books):
            for k, part in zip(books, np.array_split(dims, len(books))):
                mask[k, part] = 1.0
    return jnp.asarray(mask)


def init_train_state(key, icq_cfg, *, embed_kind: str = "linear",
                     d_raw: Optional[int] = None, num_classes: int = 10,
                     img_hw: Optional[int] = None, channels: Optional[int] = None,
                     mode: str = "icq", lr: float = 1e-3,
                     sample_batch=None) -> Dict:
    """Build params + optimizer + variance state.  ``sample_batch`` (x, y)
    seeds the codebooks from real embeddings (residual k-means; for ICQ
    on the supports of ``_icq_supports``) and sets
    the state's ``unit`` (``fit_unit``; 1.0 without a sample): codebooks,
    prior and variance state live in embedding units times ``unit``, and
    ``make_train_step`` and ``finalize`` take it as ``unit=``."""
    d, K, m = icq_cfg.d, icq_cfg.num_codebooks, icq_cfg.codebook_size
    k_embed, k_cb, k3 = jax.random.split(key, 3)
    embed_params, embed_apply = embed_mod.build_embedder(
        embed_kind, k_embed, d_raw=d_raw, d=d, num_classes=num_classes,
        img_hw=img_hw, channels=channels)

    theta0 = prior_mod.init_theta()
    unit = 1.0
    if sample_batch is not None:
        emb0 = embed_apply(embed_params, sample_batch[0])
        unit = fit_unit(emb0)
        emb0 = _in_unit(emb0, unit)
        lam0 = jnp.var(emb0, axis=0)
        theta0 = prior_mod.init_theta_from_data(lam0)
        if mode == "pq":
            C0 = cb.init_pq(k_cb, emb0, K, m)
        elif mode == "icq":
            C0 = cb.init_residual(k_cb, emb0, K, m,
                                  mask=_icq_supports(lam0, theta0, icq_cfg))
        else:
            C0 = cb.init_residual(k_cb, emb0, K, m)
    else:
        C0 = jax.random.normal(k_cb, (K, m, d), jnp.float32) * 0.1

    params = {"embed": embed_params, "C": C0, "theta": theta0}
    opt = AdamW(lr=lambda step: jnp.asarray(lr, jnp.float32),
                weight_decay=0.0, clip_norm=1.0)
    return {
        "params": params,
        "opt_state": opt.init(params),
        "var_state": variance.init_state(d),
        "opt": opt,
        "embed_apply": embed_apply,
        "mode": mode,
        "pq_mask": _pq_support_mask(K, d) if mode == "pq" else None,
        "unit": unit,
    }


def _soft_xi(lam, theta, icq_cfg):
    """Minor-mode posterior responsibility — the differentiable xi."""
    log_major, log_minor = prior_mod.mode_log_components(
        lam, theta, pi1=icq_cfg.pi1, pi2=icq_cfg.pi2, alpha2=icq_cfg.alpha2)
    return jax.nn.sigmoid(log_minor - log_major)


def make_train_step(icq_cfg, embed_apply, opt: AdamW, mode: str,
                    pq_mask=None, tau: float = 1.0,
                    axis_name: Optional[str] = None, *, unit: float):
    """Returns jit-able step(params, opt_state, var_state, batch) ->
    (params, opt_state, var_state, metrics).

    ``unit``: the train state's ``unit``; every loss term sees the
    embeddings times it.

    ``axis_name`` (optional): the mesh axis of a data-parallel region
    the step runs inside (``trainer.epoch`` shard_map driver).  Batch
    moments for the Lambda update become global (pmean of shard
    moments — exact for the driver's equal shards) and gradients are
    pmean'd, so parameters and variance state stay replicated without
    any extra synchronization."""

    def loss_fn(params, var_state, x, y):
        emb = _in_unit(embed_apply(params["embed"], x), unit)
        # --- L^E ---
        logits = embed_mod.classify(params["embed"], emb)
        l_e = losses.classification_loss(logits, y)
        # --- online variance with straight-through running value ---
        m_b, lam_batch = variance.global_batch_moments(emb, axis_name)
        nb = emb.shape[0] if axis_name is None else (
            emb.shape[0] * jax.lax.psum(1, axis_name))
        new_var = variance.update_from_moments(var_state, m_b, lam_batch, nb)
        lam = (jax.lax.stop_gradient(variance.lambda_hat(new_var) - lam_batch)
               + lam_batch)
        # --- L^C ---
        l_c, codes = losses.quantization_loss(emb, params["C"], tau)
        total = l_e + l_c
        mets = {"l_e": l_e, "l_c": l_c}
        if mode in ("icq", "cq"):
            l_cq, _ = losses.cq_penalty(params["C"], codes)
            total = total + icq_cfg.gamma_cq * l_cq
            mets["l_cq"] = l_cq
        if mode == "icq":
            l_p = prior_mod.nll(lam, params["theta"], pi1=icq_cfg.pi1,
                                pi2=icq_cfg.pi2, alpha2=icq_cfg.alpha2)
            xi_soft = _soft_xi(jax.lax.stop_gradient(lam), params["theta"],
                               icq_cfg)
            l_icq = losses.icq_loss(params["C"], xi_soft)
            total = total + icq_cfg.gamma_p * l_p + icq_cfg.gamma_icq * l_icq
            mets.update(l_p=l_p, l_icq=l_icq, psi_size=jnp.sum(xi_soft > 0.5))
        mets["total"] = total
        return total, (new_var, mets)

    def step(params, opt_state, var_state, batch):
        x, y = batch
        grads, (new_var, mets) = jax.grad(loss_fn, has_aux=True)(
            params, var_state, x, y)
        if axis_name is not None:
            # data-parallel: mean-of-shard-grads == grad of the global
            # batch mean loss (equal shard sizes); metrics follow suit
            grads = jax.lax.pmean(grads, axis_name)
            mets = jax.lax.pmean(mets, axis_name)
        if mode == "icq":
            # Theta must track the (moving) variance distribution faster
            # than W reshapes it, or the mixture collapses to one mode
            # (§3.3); 3 scalars, so the boosted rate is cheap and safe.
            grads = dict(grads, theta=jax.tree.map(
                lambda g: g * 10.0, grads["theta"]))
        params, opt_state, gnorm = opt.update(grads, opt_state, params)
        if mode == "pq":                      # hard support projection
            params = dict(params, C=params["C"] * pq_mask[:, None, :])
        mets["gnorm"] = gnorm
        return params, opt_state, new_var, mets

    return step


def finalize(params, embed_apply, var_state, icq_cfg, xs, *, unit: float,
             mode="icq", encode_batch: int = 8192,
             encode_backend: str = "auto", interpret=None) -> ICQModel:
    """Export: hard-project codebooks (ICQ), ICM-encode the database
    through the tiled engine (DESIGN.md §9), build the search structure.
    ``unit`` is the train state's: the structure is decided in the fit's
    units, then codebooks, Lambda and sigma return to the embedding's.

    ``encode_batch`` chunks the database through one fixed-shape jitted
    embed+encode function — the ragged last chunk is zero-padded up to
    the chunk size and the pad rows masked out of the stored codes, so
    the encode function compiles exactly once.  ``encode_backend``
    follows the engine dispatch ("jnp" | "pallas" | "auto")."""
    from repro.trainer.encode import encode_database

    lam = variance.lambda_hat(var_state)
    C = params["C"]
    if mode == "icq":
        structure = icq_mod.build_structure(C, lam, params["theta"], icq_cfg)
        C = icq_mod.project_codebooks(C, structure.xi, structure.fast_mask)
        # rebuild with projected C (fast set/energies unchanged by projection)
        structure = icq_mod.ICQStructure(
            xi=structure.xi, fast_mask=structure.fast_mask,
            sigma=structure.sigma)
    else:
        xi = prior_mod.psi_mask_topk(lam, max(1, icq_cfg.d // 2))
        structure = icq_mod.ICQStructure(
            xi=xi, fast_mask=jnp.ones((C.shape[0],), bool),
            sigma=jnp.zeros(()))
    if unit != 1.0:
        C, lam = C / unit, lam / unit ** 2
        structure = structure._replace(sigma=structure.sigma / unit ** 2)

    codes = encode_database(
        xs, C, embed_apply=embed_apply, embed_params=params["embed"],
        mode="pq" if mode == "pq" else "icm", icm_iters=icq_cfg.icm_iters,
        chunk=encode_batch, backend=encode_backend, interpret=interpret)
    return ICQModel(icq_cfg=icq_cfg, embed_params=params["embed"],
                    embed_apply=embed_apply, C=C, codes=codes,
                    structure=structure, lam=lam, mode=mode)
