"""Quantizer implementations behind the trainer protocol (DESIGN.md §9).

``JointQuantizer`` wraps the joint trainer (mode="icq" | "cq" | "pq" —
the ICQ system plus the SQ and PQN supervised baselines).  The
unsupervised baselines PQ / OPQ / CQ implement the same
init/step/finalize verbs: closed-form or round-based ``step``s, and a
``finalize`` that exports through the tiled encoding engine.  The
historical ``fit_*`` entry points (re-exported by ``core/baselines/*``)
are thin drivers over these classes — behavior and seeds unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import codebooks as cb
from repro.core import encode as enc
from repro.core import losses
from repro.trainer import joint
from repro.trainer.base import ICQModel, plain_structure
from repro.trainer.encode import encode_database
from repro.train.optimizer import AdamW


@dataclasses.dataclass
class JointQuantizer:
    """The joint embedding+codebook trainer as a protocol Quantizer.

    mode="icq" is the paper's system; mode="cq" with the linear embedder
    is SQ (Wang et al.); mode="pq" with the CNN embedder is PQN-style
    (Yu et al.).  ``step`` is one SGD step on a (x, y) minibatch — the
    epoch driver (``trainer.epoch``) compiles stacks of them into one
    scan."""
    icq_cfg: object
    mode: str = "icq"
    embed_kind: str = "linear"
    num_classes: int = 10
    img_hw: Optional[int] = None
    channels: Optional[int] = None
    lr: float = 1e-3
    tau: float = 1.0
    sample_size: int = 4096

    def init(self, key, xs, ys=None) -> Dict:
        n = xs.shape[0]
        st = joint.init_train_state(
            key, self.icq_cfg, embed_kind=self.embed_kind,
            d_raw=xs.shape[-1] if xs.ndim == 2 else None,
            num_classes=self.num_classes, img_hw=self.img_hw,
            channels=self.channels, mode=self.mode, lr=self.lr,
            sample_batch=(xs[:min(n, self.sample_size)],
                          ys[:min(n, self.sample_size)]))
        st["step_fn"] = jax.jit(joint.make_train_step(
            self.icq_cfg, st["embed_apply"], st["opt"], self.mode,
            st["pq_mask"], self.tau, unit=st["unit"]))
        return st

    def step(self, state: Dict, batch) -> Dict:
        p, o, v, mets = state["step_fn"](state["params"],
                                         state["opt_state"],
                                         state["var_state"], batch)
        return dict(state, params=p, opt_state=o, var_state=v,
                    last_metrics=mets)

    def finalize(self, state: Dict, xs) -> ICQModel:
        return joint.finalize(state["params"], state["embed_apply"],
                              state["var_state"], self.icq_cfg, xs,
                              unit=state["unit"], mode=self.mode)


@dataclasses.dataclass
class PQQuantizer:
    """Product Quantization (Jegou, Douze, Schmid 2010).

    Unsupervised and closed-form: ``init`` fits k-means per contiguous
    subspace on the given sample; ``step`` is the identity (kept for
    protocol uniformity); ``finalize`` encodes independently per
    codebook through the engine."""
    icq_cfg: object
    kmeans_iters: int = 25
    embed_params: object = None
    embed_apply: object = None

    def _apply(self):
        return self.embed_apply or (lambda p, x: x)

    def init(self, key, xs, ys=None) -> Dict:
        emb = self._apply()(self.embed_params, xs)
        C = cb.init_pq(key, emb, self.icq_cfg.num_codebooks,
                       self.icq_cfg.codebook_size, self.kmeans_iters)
        return {"C": C}

    def step(self, state: Dict, batch) -> Dict:
        return state                          # closed-form at init

    def finalize(self, state: Dict, xs) -> ICQModel:
        apply_fn = self._apply()
        emb = apply_fn(self.embed_params, xs)
        C = state["C"]
        codes = encode_database(emb, C, mode="pq")
        return ICQModel(icq_cfg=self.icq_cfg, embed_params=self.embed_params,
                        embed_apply=apply_fn, C=C, codes=codes,
                        structure=plain_structure(C, emb.shape[-1]),
                        lam=jnp.var(emb, axis=0), mode="pq")


@dataclasses.dataclass
class OPQQuantizer:
    """Optimized Product Quantization (Ge et al. 2013) — non-parametric.

    ``step`` is one alternation round on its batch: (1) PQ in the
    rotated space R x; (2) rotation update by the orthogonal Procrustes
    solution R = U V^T from SVD(X^T Xbar).  ``finalize`` folds the
    learned R into the embedding apply so search-side code is shared
    with plain PQ."""
    icq_cfg: object
    kmeans_iters: int = 10
    embed_params: object = None
    embed_apply: object = None

    def _apply(self):
        return self.embed_apply or (lambda p, x: x)

    def init(self, key, xs, ys=None) -> Dict:
        emb = self._apply()(self.embed_params, xs).astype(jnp.float32)
        return {"R": jnp.eye(emb.shape[-1], dtype=jnp.float32), "C": None,
                "key": key, "round": 0}

    def step(self, state: Dict, batch) -> Dict:
        emb = batch[0] if isinstance(batch, tuple) else batch
        emb = self._apply()(self.embed_params, emb).astype(jnp.float32)
        xr = emb @ state["R"]
        C = cb.init_pq(jax.random.fold_in(state["key"], state["round"]), xr,
                       self.icq_cfg.num_codebooks,
                       self.icq_cfg.codebook_size, self.kmeans_iters)
        codes = enc.encode_pq(xr, C)
        xbar = cb.decode(C, codes)
        # Procrustes: maximize tr(R^T X^T Xbar)  ->  R = U V^T
        u, s, vt = jnp.linalg.svd(emb.T @ xbar, full_matrices=False)
        return dict(state, R=u @ vt, C=C, round=state["round"] + 1)

    def finalize(self, state: Dict, xs) -> ICQModel:
        base_apply = self._apply()
        emb = base_apply(self.embed_params, xs).astype(jnp.float32)
        xr = emb @ state["R"]
        C = state["C"]
        codes = encode_database(xr, C, mode="pq")
        ep = {"base": self.embed_params, "R": state["R"]}

        def apply_fn(p, x):
            return base_apply(p["base"], x) @ p["R"]

        return ICQModel(icq_cfg=self.icq_cfg, embed_params=ep,
                        embed_apply=apply_fn, C=C, codes=codes,
                        structure=plain_structure(C, emb.shape[-1]),
                        lam=jnp.var(xr, axis=0), mode="pq")


@dataclasses.dataclass
class CQQuantizer:
    """Composite Quantization (Zhang, Du, Wang 2014) — unsupervised.

    Additive codebooks with the constant-inner-product constraint;
    ``step`` is one round of ``grad_steps`` gradient updates on C
    followed by ICM re-encoding (warm-started from the previous codes,
    through the tiled engine)."""
    icq_cfg: object
    grad_steps: int = 50
    lr: float = 5e-3
    embed_params: object = None
    embed_apply: object = None

    def _apply(self):
        return self.embed_apply or (lambda p, x: x)

    def init(self, key, xs, ys=None) -> Dict:
        emb = self._apply()(self.embed_params, xs).astype(jnp.float32)
        C = cb.init_residual(key, emb, self.icq_cfg.num_codebooks,
                             self.icq_cfg.codebook_size, iters=10)
        codes = enc.icm_encode(emb, C, self.icq_cfg.icm_iters)
        opt = AdamW(lr=lambda s: jnp.asarray(self.lr), weight_decay=0.0,
                    clip_norm=0.0)
        gamma = self.icq_cfg.gamma_cq

        def loss_fn(C, codes, emb):
            rec = cb.decode(C, codes)
            l_rec = jnp.mean(jnp.sum(jnp.square(emb - rec), axis=-1))
            l_cq, _ = losses.cq_penalty(C, codes)
            return l_rec + gamma * l_cq

        @jax.jit
        def c_steps(C, codes, opt_state, emb):
            def body(carry, _):
                C, opt_state = carry
                g = jax.grad(loss_fn)(C, codes, emb)
                params, opt_state, _ = opt.update({"C": g}, opt_state,
                                                  {"C": C})
                return (params["C"], opt_state), None
            (C, opt_state), _ = jax.lax.scan(body, (C, opt_state), None,
                                             length=self.grad_steps)
            return C, opt_state

        encode_jit = jax.jit(lambda e, C, codes: enc.icm_encode(
            e, C, self.icq_cfg.icm_iters, init_codes=codes))
        return {"C": C, "codes": codes, "opt_state": opt.init({"C": C}),
                "c_steps": c_steps, "encode": encode_jit}

    def step(self, state: Dict, batch) -> Dict:
        emb = batch[0] if isinstance(batch, tuple) else batch
        emb = self._apply()(self.embed_params, emb).astype(jnp.float32)
        C, opt_state = state["c_steps"](state["C"], state["codes"],
                                        state["opt_state"], emb)
        codes = state["encode"](emb, C, state["codes"])
        return dict(state, C=C, codes=codes, opt_state=opt_state)

    def finalize(self, state: Dict, xs) -> ICQModel:
        apply_fn = self._apply()
        emb = apply_fn(self.embed_params, xs).astype(jnp.float32)
        C = state["C"]
        codes = enc.pack_codes(state["codes"], self.icq_cfg.codebook_size)
        return ICQModel(icq_cfg=self.icq_cfg, embed_params=self.embed_params,
                        embed_apply=apply_fn, C=C, codes=codes,
                        structure=plain_structure(C, emb.shape[-1]),
                        lam=jnp.var(emb, axis=0), mode="cq")


# ------------------------------------------------- historical fit_* entries

def fit_pq(key, xs, icq_cfg, *, kmeans_iters: int = 25,
           embed_params=None, embed_apply=None) -> ICQModel:
    """Fit PQ on raw vectors (or pre-embedded if embed_* given)."""
    q = PQQuantizer(icq_cfg, kmeans_iters=kmeans_iters,
                    embed_params=embed_params, embed_apply=embed_apply)
    return q.finalize(q.init(key, xs), xs)


def fit_opq(key, xs, icq_cfg, *, rounds: int = 8, kmeans_iters: int = 10,
            embed_params=None, embed_apply=None) -> ICQModel:
    """Fit OPQ: ``rounds`` alternation steps over the full data."""
    q = OPQQuantizer(icq_cfg, kmeans_iters=kmeans_iters,
                     embed_params=embed_params, embed_apply=embed_apply)
    state = q.init(key, xs)
    for _ in range(rounds):
        state = q.step(state, xs)
    return q.finalize(state, xs)


def fit_cq(key, xs, icq_cfg, *, rounds: int = 10, grad_steps: int = 50,
           lr: float = 5e-3, embed_params=None, embed_apply=None) -> ICQModel:
    """Fit CQ: ``rounds`` (C-gradient + ICM re-encode) rounds."""
    q = CQQuantizer(icq_cfg, grad_steps=grad_steps, lr=lr,
                    embed_params=embed_params, embed_apply=embed_apply)
    state = q.init(key, xs)
    for _ in range(rounds):
        state = q.step(state, xs)
    return q.finalize(state, xs)
