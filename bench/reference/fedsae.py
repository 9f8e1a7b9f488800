"""Plain reference of a FedSAE run on the scan driver, for the benchmark's
correctness check.

It implements the same semantics as the program from the published
description and the program's documented conventions, and shares no code
with it: it imports nothing from ``repro`` and takes nothing the program
has made.  Its inputs are the federation itself (the numpy client arrays
the generator returned) and the seeds.  Every round:

1. Heterogeneity (FedSAE section IV-A): client k affords
   E~ = max(mu_k + sigma_k * N(0, 1), 0) epochs, with mu_k ~ U[5, 10) and
   sigma_k ~ U[mu_k / 4, mu_k / 2) drawn once from numpy's PCG64 at
   ``seed``.
2. Selection: K distinct clients uniformly at random, as a Gumbel top-k.
3. Prediction: FedSAE-Fassa (Alg. 3, with line 23 read as min(L + r2, H/2))
   or FedAvg's fixed workload; the uploaded epochs e and the outcome.
4. Budget: n_iters = min(round(e * ceil(n_k / B)), max_iters) minibatch
   steps of B samples drawn uniformly with replacement.
5. Local SGD on the model's masked-mean softmax cross-entropy; the
   client's loss is the mean minibatch loss over its executed steps.
6. Upload transform (optional top-k + int8 with error feedback) and
   FedAvg weighted by n_k over the clients that trained at least one step.

The random streams follow the program's documented key discipline
(threefry keys ``PRNGKey(selection_seed)`` for selection and heterogeneity,
``PRNGKey(seed)`` for minibatches, ``PRNGKey(seed + 7)`` for the initial
weights), so that a sound program and this reference select the same
cohorts, assign the same budgets and draw the same minibatches.

Every matmul runs at ``precision="highest"``.  ``dtype=jnp.bfloat16`` runs
the same rounds with params, data and arithmetic in bfloat16: that is the
control, the lower precision a later change might be tempted by.
``half_batch=True`` leaves half of every minibatch out and averages over
the rest: one of the faults the check has to catch.
"""
from __future__ import annotations

import math
import importlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


COMPLETED_H, COMPLETED_L, DROPPED = 2, 1, 0
F32 = jnp.float32
INIT_PAIR = (1.0, 2.0)          # the server's initial (L, H)
SELECTION_SEED = 1234           # fixed across frameworks (FedSAE IV-A)


def het_params(n_clients: int, seed: int):
    """Per-client (mu, sigma) of the affordable-workload Gaussian."""
    rng = np.random.default_rng(seed)
    mu = rng.uniform(5.0, 10.0, n_clients)
    sigma = rng.uniform(0.25 * mu, 0.5 * mu)
    return mu.astype(np.float32), sigma.astype(np.float32)


def _clamp(L, H, h_cap):
    L = jnp.maximum(L, F32(0.25))
    H = jnp.maximum(H, L + F32(1e-3))
    return jnp.minimum(L, F32(h_cap)), jnp.minimum(H, F32(h_cap))


def predict(algo: str, L, H, theta, E, tr: Dict):
    """Workload step for the cohort rows: (e, outcome, assigned, L', H',
    theta') — float32, rows only."""
    if algo == "fedavg":
        F = F32(tr["fixed_epochs"])
        ok = E >= F
        return (jnp.where(ok, F, F32(0.0)),
                jnp.where(ok, COMPLETED_H, DROPPED),
                jnp.full_like(E, F), L, H, theta)
    if algo != "fassa":
        raise ValueError(f"reference covers fassa and fedavg, not {algo!r}")
    out = jnp.where(E >= H, COMPLETED_H, jnp.where(E >= L, COMPLETED_L,
                                                   DROPPED))
    e = jnp.where(out == COMPLETED_H, H,
                  jnp.where(out == COMPLETED_L, L, F32(0.0)))
    r1, r2 = F32(tr.get("gamma1", 3.0)), F32(tr.get("gamma2", 1.0))
    half = F32(0.5)
    L_s = jnp.where(theta <= L, L + r2, jnp.where(theta <= H, L + r1, L + r2))
    H_s = jnp.where(theta <= L, H + r2, jnp.where(theta <= H, H + r2, H + r1))
    inc = jnp.where(theta <= L, r2, r1)
    L_p = jnp.minimum(L + inc, half * H)
    H_p = jnp.maximum(L + inc, half * H)
    L2 = jnp.where(out == COMPLETED_H, L_s,
                   jnp.where(out == COMPLETED_L, L_p, half * L))
    H2 = jnp.where(out == COMPLETED_H, H_s,
                   jnp.where(out == COMPLETED_L, H_p, half * H))
    L2, H2 = _clamp(L2, H2, tr["h_cap"])
    a = F32(tr.get("alpha", 0.95))
    theta2 = a * theta + (F32(1.0) - a) * E
    return e, out, H, L2, H2, theta2


def topk_q8(ef, k: int):
    """Keep each row's k largest magnitudes (earliest index on ties) as
    int8 codes of one symmetric per-row scale; returns the transmitted
    values code * scale."""
    P = ef.shape[-1]
    a = jnp.abs(ef)
    scale = jnp.max(a, axis=-1) * jnp.asarray(1.0 / 127.0, ef.dtype)
    if k >= P:
        keep = jnp.ones(ef.shape, bool)
    else:
        _, top = jax.lax.top_k(a, k)
        keep = jnp.zeros(ef.shape, bool).at[
            jnp.arange(ef.shape[0])[:, None], top].set(True)
    safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
    q = jnp.clip(jnp.round(ef / safe[:, None]), -127, 127)
    q = jnp.where(keep & (scale[:, None] > 0), q, 0)
    return q * scale[:, None]


class Reference:
    """The reference run of one cell from one seed."""

    def __init__(self, clients_x: Sequence[np.ndarray],
                 clients_y: Sequence[np.ndarray], model_cfg: Dict,
                 traffic: Dict, seed: int, dtype=F32,
                 half_batch: bool = False):
        self.tr = traffic
        self.seed = int(seed)
        self.dtype = dtype
        sizes = np.array([len(y) for y in clients_y])
        self.N = len(sizes)
        self.B = int(traffic["batch_size"])
        self.K = int(traffic["n_selected"])
        self.max_n = int(sizes.max())
        budget = max(float(traffic["h_cap"]),
                     float(traffic.get("fixed_epochs", 15.0)))
        self.max_iters = int(math.ceil(budget
                                       * math.ceil(self.max_n / self.B)))
        offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        x = np.concatenate(list(clients_x))
        self.x = (jnp.asarray(x, dtype)
                  if np.issubdtype(x.dtype, np.floating) else jnp.asarray(x))
        self.y = jnp.asarray(np.concatenate(list(clients_y)), jnp.int32)
        self.offsets = jnp.asarray(offsets, jnp.int32)
        self.sizes = jnp.asarray(sizes, jnp.int32)
        mu, sigma = het_params(self.N, self.seed)
        self.mu, self.sigma = jnp.asarray(mu), jnp.asarray(sigma)
        self.half_batch = half_batch
        model = importlib.import_module(f"reference.{model_cfg['model']}")
        self.loss_fn = model.loss
        with jax.default_matmul_precision("highest"):
            self.params = jax.tree.map(
                lambda p: p.astype(dtype),
                model.init(model_cfg, jax.random.PRNGKey(self.seed + 7)))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(self.params))
        self.compress = traffic.get("upload_compress", "none") == "topk_q8"
        self.k = max(0, min(int(math.ceil(float(traffic.get(
            "topk_frac", 0.1)) * n_params)), n_params))
        self.residual = (jnp.zeros((self.N, n_params), dtype)
                         if self.compress else None)
        L0, H0 = INIT_PAIR
        self.L = jnp.full((self.N,), L0, F32)
        self.H = jnp.full((self.N,), H0, F32)
        self.theta = jnp.full((self.N,), 0.5 * (L0 + H0), F32)
        self.sel_key = jax.random.PRNGKey(SELECTION_SEED)
        self.data_key = jax.random.PRNGKey(self.seed)
        self._schedule = jax.jit(self._schedule_fn)
        self._train = jax.jit(self._train_fn)

    # -- server-side step: heterogeneity, selection, prediction, budget --
    def _schedule_fn(self, sel_key, data_key, L, H, theta):
        sel_key, k_sel, k_het = jax.random.split(sel_key, 3)
        E_all = jnp.maximum(
            self.mu + self.sigma * jax.random.normal(k_het, (self.N,), F32),
            F32(0.0))
        _, ids = jax.lax.top_k(jax.random.gumbel(k_sel, (self.N,), F32),
                               self.K)
        E = E_all[ids]
        e, out, assigned, L2, H2, th2 = predict(
            self.tr["algo"], L[ids], H[ids], theta[ids], E, self.tr)
        n = jnp.minimum(self.sizes[ids], self.max_n)
        tau = jnp.ceil(n.astype(F32) / F32(self.B))
        n_iters = jnp.minimum(jnp.round(e * tau),
                              self.max_iters).astype(jnp.int32)
        data_key, sub = jax.random.split(data_key)
        return (sel_key, data_key, L.at[ids].set(L2), H.at[ids].set(H2),
                theta.at[ids].set(th2), ids, n, n_iters, sub,
                {"dropped": (out == DROPPED).sum(),
                 "assigned": assigned.mean(), "uploaded": e.mean()})

    # -- client-side step: local SGD, upload transform, aggregation ----
    def _train_fn(self, params, residual, ids, n, n_iters, sub, x, y,
                  offsets):
        # the federation comes in as arguments: captured, it would be
        # baked into the program as a constant hundreds of MB large
        dt, B, lr = self.dtype, self.B, self.tr["lr"]
        keys = jax.random.split(sub, self.K)
        steps = jnp.max(n_iters)
        rows = B // 2 if self.half_batch else B

        def client(off, nk, iters, key):
            nk = jnp.maximum(nk, 1)
            idx = jax.random.randint(key, (self.max_iters, B), 0, nk)
            mask = (jnp.arange(B) < jnp.minimum(nk, rows)).astype(dt)

            def body(i, carry):
                p, lsum = carry
                rows_i = off + idx[i]
                loss, g = jax.value_and_grad(self.loss_fn)(
                    p, x[rows_i], y[rows_i], mask)
                act = i < iters
                p = jax.tree.map(
                    lambda a, b: jnp.where(act, a - jnp.asarray(lr, dt) * b,
                                           a), p, g)
                return p, lsum + jnp.where(act, loss.astype(F32), F32(0.0))

            p, lsum = jax.lax.fori_loop(0, steps, body,
                                        (params, F32(0.0)))
            return p, lsum / jnp.maximum(iters, 1).astype(F32)

        p_k, losses = jax.vmap(client)(offsets[ids], n, n_iters, keys)
        up = n_iters > 0
        leaves, treedef = jax.tree.flatten(params)
        g = jnp.concatenate([l.reshape(-1) for l in leaves])
        stack = jnp.concatenate(
            [l.reshape(self.K, -1) for l in jax.tree.leaves(p_k)], axis=1)
        if self.compress:
            ef = stack - g[None] + residual[ids]
            sent = jnp.where(up[:, None], topk_q8(ef, self.k), 0)
            residual = residual.at[ids].set(
                jnp.where(up[:, None], ef - sent, residual[ids]))
            stack = g[None] + sent
        w = n.astype(F32) * up.astype(F32)
        tot = w.sum()
        coef = (w / jnp.maximum(tot, F32(1e-9))).astype(dt)
        new = jnp.where(tot > 0, coef @ stack, g)
        out, pos = [], 0
        for l in leaves:
            out.append(new[pos:pos + l.size].reshape(l.shape))
            pos += l.size
        n_up = up.sum()
        loss = jnp.where(n_up > 0, (losses * up).sum()
                         / jnp.maximum(n_up, 1), jnp.nan)
        return jax.tree.unflatten(treedef, out), residual, loss

    def run(self, rounds: int, snapshots: Sequence[int]) -> Dict:
        """Run ``rounds`` rounds; params are kept after each round count in
        ``snapshots``.  Returns per-round stats and the snapshots."""
        stats: Dict[str, List] = {"ids": [], "dropped": [], "assigned": [],
                                  "uploaded": [], "train_loss": []}
        snaps = {0: self.params} if 0 in snapshots else {}
        with jax.default_matmul_precision("highest"):
            for t in range(rounds):
                (self.sel_key, self.data_key, self.L, self.H, self.theta,
                 ids, n, n_iters, sub, s) = self._schedule(
                    self.sel_key, self.data_key, self.L, self.H, self.theta)
                self.params, self.residual, loss = self._train(
                    self.params, self.residual, ids, n, n_iters, sub,
                    self.x, self.y, self.offsets)
                stats["ids"].append(ids)
                stats["train_loss"].append(loss)
                for key in ("dropped", "assigned", "uploaded"):
                    stats[key].append(s[key])
                if t + 1 in snapshots:
                    snaps[t + 1] = self.params
        stats = {k: np.asarray(jax.device_get(v)) for k, v in stats.items()}
        stats["params"] = {t: jax.tree.map(
            lambda a: np.asarray(a, np.float32), jax.device_get(p))
            for t, p in snaps.items()}
        return stats
