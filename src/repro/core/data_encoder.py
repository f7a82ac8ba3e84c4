"""Correlation-Aware Vector–Scalar Data Encoder (paper §3.2).

Per vector column i:
  * M **frozen** MLPs ``f_frozen[i,j]`` — each trained to predict scalar j
    (binned, cross-entropy) from vector i, then frozen. Their softmax outputs
    embed scalar-relevant structure into the vector representation.
  * one **trainable** MLP ``f_trainable[i]`` — trained end-to-end with the
    autoencoder.

``E_i = [‖_j f_frozen[i,j](v_i) ; f_trainable[i](v_i) ; E_s]`` feeds a shared
autoencoder trained on reconstruction MSE. At query time the reconstruction
error of the (query-vector, predicate-encoding) pairing is the anomaly score
ε_recon_i consumed by the rewriter.

Incremental updates (paper §3.2 'Incremental Model Updates'): ``update()``
fine-tunes on the inserted rows only — frozen nets get a short refresh, the
AE continues training; no full retraining pass.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import nn
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_step
from repro.vectordb.predicates import PredicateLike, soft_encode, value_encode
from repro.vectordb.table import Table


@dataclasses.dataclass(frozen=True)
class DataEncoderConfig:
    n_bins: int = 16  # one-hot bins per scalar (encoder-side)
    frozen_hidden: int = 32
    trainable_dim: int = 16
    ae_hidden: int = 64
    ae_latent: int = 24
    lr: float = 2e-3
    frozen_steps: int = 200
    ae_steps: int = 400
    update_steps: int = 80  # incremental fine-tune budget
    batch: int = 512
    sample: int = 8192  # sampled subset for initial training (paper §3.5)
    seed: int = 0


def _quantile_edges(scalars: np.ndarray, n_bins: int) -> np.ndarray:
    """(n, M) -> (M, B+1) quantile bin edges (robust to skewed marginals)."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)
    edges = np.quantile(scalars, qs, axis=0).T.astype(np.float32)
    # ensure strictly increasing edges
    eps = 1e-6 * (1.0 + np.abs(edges))
    edges = np.maximum.accumulate(edges + eps * np.arange(n_bins + 1)[None, :], axis=1)
    return edges


# ---------------------------------------------------------------------------
# stacked frozen predictors (per vector column: M nets, vmapped over j)
# ---------------------------------------------------------------------------

def _frozen_init(key, d_in: int, m: int, cfg: DataEncoderConfig):
    k1, k2 = jax.random.split(key)
    h, b = cfg.frozen_hidden, cfg.n_bins
    return {
        "w0": nn.trunc_normal(k1, (m, d_in, h), 1.0 / np.sqrt(d_in)),
        "b0": jnp.zeros((m, h)),
        "w1": nn.trunc_normal(k2, (m, h, b), 1.0 / np.sqrt(h)),
        "b1": jnp.zeros((m, b)),
    }


def _frozen_apply(p, v):
    """v: (..., d) -> (..., M, B) softmax probabilities."""
    h = jax.nn.relu(jnp.einsum("...d,mdh->...mh", v, p["w0"]) + p["b0"])
    logits = jnp.einsum("...mh,mhb->...mb", h, p["w1"]) + p["b1"]
    return jax.nn.softmax(logits, axis=-1)


def _frozen_logits(p, v):
    h = jax.nn.relu(jnp.einsum("...d,mdh->...mh", v, p["w0"]) + p["b0"])
    return jnp.einsum("...mh,mhb->...mb", h, p["w1"]) + p["b1"]


# ---------------------------------------------------------------------------
# the encoder
# ---------------------------------------------------------------------------

class DataEncoder:
    """Holds params + bin edges; provides fit / update / recon_error."""

    def __init__(self, vec_dims: list[int], n_scalars: int, cfg: DataEncoderConfig):
        self.cfg = cfg
        self.vec_dims = list(vec_dims)
        self.m = n_scalars
        self.edges: Optional[jnp.ndarray] = None  # (M, B+1)
        self.params: dict = {}
        b, t = cfg.n_bins, cfg.trainable_dim
        self.embed_dim = self.m * b + t + self.m * b  # E_vi ; E_s

    # -- embeddings ---------------------------------------------------------

    def _evec(self, params, i: int, v: jax.Array) -> jax.Array:
        """E_vi = [frozen probs (M·B) ; trainable (T)] for column i."""
        fr = _frozen_apply(params["frozen"][i], v)  # (..., M, B)
        fr = fr.reshape(*fr.shape[:-2], -1)
        tr = nn.mlp_apply(params["trainable"][i], v)
        return jnp.concatenate([fr, tr], axis=-1)

    def _ae(self, params, e: jax.Array) -> jax.Array:
        z = nn.mlp_apply(params["ae_enc"], e)
        return nn.mlp_apply(params["ae_dec"], z)

    def _ae_loss(self, train_params, frozen_logits: tuple, vecs: tuple,
                 es: jax.Array):
        """Autoencoder reconstruction loss of one batch: the trainable nets
        and the AE are trained; the frozen predictors' logits come in
        precomputed (per column (batch, M, B)), so the frozen nets stay out
        of the differentiated program."""
        loss = 0.0
        for i in range(len(self.vec_dims)):
            fr = jax.nn.softmax(frozen_logits[i], axis=-1)
            tr = nn.mlp_apply(train_params["trainable"][i], vecs[i])
            e = jnp.concatenate([fr.reshape(fr.shape[0], -1), tr, es], axis=-1)
            loss = loss + jnp.mean(jnp.square(self._ae(train_params, e) - e))
        return loss / len(self.vec_dims)

    @staticmethod
    def _frozen_batch_logits(frozen, vecs: tuple) -> tuple:
        """Per column, the frozen predictors' (batch, M, B) logits. The
        softmax is left to ``_ae_loss``: at a 512-row batch, a softmax
        fused after these batched contractions overflows the stack of XLA's
        TPU compiler (v5e, jax 0.9)."""
        return tuple(_frozen_logits(frozen[i], v) for i, v in enumerate(vecs))

    def embed_rows(self, i: int, vecs: jax.Array, scalars: jax.Array) -> jax.Array:
        es = jax.vmap(lambda s: value_encode(s, self.edges).reshape(-1))(scalars)
        ev = self._evec(self.params, i, vecs)
        return jnp.concatenate([ev, es], axis=-1)

    # -- training -----------------------------------------------------------

    def fit(self, table: Table) -> dict:
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed)
        n = table.n_rows
        sub = np.random.default_rng(cfg.seed).choice(n, min(cfg.sample, n), replace=False)
        scal_np = np.asarray(table.scalars)[sub]
        self.edges = jnp.asarray(_quantile_edges(np.asarray(table.scalars), cfg.n_bins))
        # bin labels for frozen training
        labels = np.stack(
            [
                np.clip(
                    np.searchsorted(np.asarray(self.edges)[j], scal_np[:, j], side="right") - 1,
                    0,
                    cfg.n_bins - 1,
                )
                for j in range(self.m)
            ],
            axis=1,
        )  # (S, M)
        labels = jnp.asarray(labels)

        keys = jax.random.split(key, 2 * len(self.vec_dims) + 2)
        params = {
            "frozen": [
                _frozen_init(keys[i], d, self.m, cfg) for i, d in enumerate(self.vec_dims)
            ],
            "trainable": [
                nn.mlp_init(keys[len(self.vec_dims) + i], [d, cfg.frozen_hidden, cfg.trainable_dim])
                for i, d in enumerate(self.vec_dims)
            ],
            "ae_enc": nn.mlp_init(keys[-2], [self.embed_dim, cfg.ae_hidden, cfg.ae_latent]),
            "ae_dec": nn.mlp_init(keys[-1], [cfg.ae_latent, cfg.ae_hidden, self.embed_dim]),
        }

        # ---- stage 1: frozen predictors (per vector column) ----
        opt_cfg = AdamWConfig(lr=cfg.lr, weight_decay=1e-4, grad_clip_norm=1.0)

        @jax.jit
        def frozen_loss(fp, v, lab):
            logits = _frozen_logits(fp, v)  # (B, M, bins)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, lab[:, :, None], axis=-1))

        metrics = {}
        rng = np.random.default_rng(cfg.seed + 1)
        for i in range(len(self.vec_dims)):
            vecs = jnp.asarray(np.asarray(table.vectors[i])[sub])
            fp = params["frozen"][i]
            st = adamw_init(fp, opt_cfg)
            train = adamw_step(frozen_loss, opt_cfg)
            for step in range(cfg.frozen_steps):
                bidx = rng.integers(0, vecs.shape[0], cfg.batch)
                fp, st, l = train(fp, st, vecs[bidx], labels[bidx])
            params["frozen"][i] = fp
            metrics[f"frozen_loss_col{i}"] = float(l)

        # ---- stage 2: trainable + AE (frozen nets held fixed) ----
        es_all = jax.vmap(lambda s: value_encode(s, self.edges).reshape(-1))(
            jnp.asarray(scal_np)
        )
        vec_subs = [jnp.asarray(np.asarray(table.vectors[i])[sub]) for i in range(len(self.vec_dims))]

        tp = {"trainable": params["trainable"], "ae_enc": params["ae_enc"], "ae_dec": params["ae_dec"]}
        st = adamw_init(tp, opt_cfg)
        train = adamw_step(self._ae_loss, opt_cfg)
        logits = jax.jit(self._frozen_batch_logits)
        for step in range(cfg.ae_steps):
            bidx = jnp.asarray(rng.integers(0, len(sub), cfg.batch))
            vb = tuple(v[bidx] for v in vec_subs)
            tp, st, l = train(tp, st, logits(params["frozen"], vb), vb,
                              es_all[bidx])
        params.update(tp)
        metrics["ae_loss"] = float(l)
        self.params = params
        return metrics

    def update(self, table: Table, new_rows: np.ndarray) -> dict:
        """Incremental fine-tune on inserted rows only (paper: O(c·M̃))."""
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed + 2)
        scal_new = jnp.asarray(np.asarray(table.scalars)[new_rows])
        es_new = jax.vmap(lambda s: value_encode(s, self.edges).reshape(-1))(scal_new)
        vec_new = [jnp.asarray(np.asarray(table.vectors[i])[new_rows]) for i in range(len(self.vec_dims))]
        params = self.params
        tp = {"trainable": params["trainable"], "ae_enc": params["ae_enc"], "ae_dec": params["ae_dec"]}
        opt_cfg = AdamWConfig(lr=cfg.lr * 0.5, weight_decay=1e-4)
        st = adamw_init(tp, opt_cfg)
        train = adamw_step(self._ae_loss, opt_cfg)
        logits = jax.jit(self._frozen_batch_logits)
        nb = scal_new.shape[0]
        l = jnp.zeros(())
        for step in range(cfg.update_steps):
            bidx = jnp.asarray(rng.integers(0, nb, min(cfg.batch, nb)))
            vb = tuple(v[bidx] for v in vec_new)
            tp, st, l = train(tp, st, logits(params["frozen"], vb), vb,
                              es_new[bidx])
        self.params = {**params, **tp}
        return {"ae_update_loss": float(l)}

    # -- query phase --------------------------------------------------------

    def recon_errors(self, query_vectors: list[jax.Array], pred: PredicateLike) -> jax.Array:
        """ε_recon per vector column for a query (paper 'Query Phase')."""
        if not hasattr(self, "_recon_jit") or self._recon_jit is None:
            def _fn(params, edges, qs, pred):
                es = soft_encode(pred, edges).reshape(-1)
                errs = []
                for i, q in enumerate(qs):
                    ev = self._evec(params, i, q)
                    e = jnp.concatenate([ev, es], axis=-1)
                    rec = self._ae(params, e)
                    errs.append(jnp.mean(jnp.square(rec - e)))
                return jnp.stack(errs)

            self._recon_jit = jax.jit(_fn)
        return self._recon_jit(self.params, self.edges, tuple(query_vectors), pred)
