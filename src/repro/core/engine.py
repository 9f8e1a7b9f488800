"""RoundEngine — the single device-resident substrate executing a federated
round for every training path in the repo.

A round is a four-stage pipeline (ISSUE 6 added the third stage):

    gather -> local SGD -> upload transform -> aggregate

  1. GATHER        the cohort's samples out of the packed federation
                   (XLA clamp-gather or the pallas fed_gather kernel);
  2. LOCAL SGD     masked budgeted minibatch training per client;
  3. UPLOAD        ``upload_compress="topk_q8"`` turns each client's delta
     TRANSFORM     into a top-k-sparsified int8 upload with a per-client
                   error-feedback residual (repro.core.compression; fused
                   pallas kernel fed_compress or its XLA twin), then
                   dense-reconstructs ``global + q * scale`` server-side.
                   ``"none"`` (default) is the identity — the stage
                   disappears and the round is bitwise the PR-5 round;
  4. AGGREGATE     pluggable (repro.core.aggregation) over the dense
                   (reconstructed) [K, ...] stack, so every aggregator —
                   fedavg/trimmed_mean/median/krum/... — works unchanged
                   under compression.

The error-feedback residual is per-CLIENT state ([N, P] replicated, or
[S, C, P] sharded with ``PackedClients`` so shard s owns its own clients'
rows).  It rides OUTSIDE the round: the host driver keeps it in server
state and passes it to the round function; the scan driver carries it
through the multi-round ``lax.scan``.  Clients that transmit nothing —
crashed (zero budget), capacity-overflowed, or simply unselected — keep
their residuals bit-unchanged; compacted lanes read/write the residual rows
of the slots they serve through the lane map.

One engine owns the three pieces every round needs, so no scenario
re-implements them (DESIGN.md §3, ISSUE 1):

  * the jitted masked-epoch local-SGD ``lax.scan`` (heterogeneous per-client
    budgets are not SPMD-able, so every client runs ``max_iters`` slots and
    updates are masked past ``n_iters_k`` — bit-identical to "client k trains
    n_iters_k iterations" with uniform control flow);
  * the vmapped client axis (K selected clients lead every array; with a
    ``mesh`` argument the client DATA axis really does shard over ``data``
    via ``shard_map`` — each shard gathers and trains only the cohort slots
    it owns and the [K] stacks are rebuilt by an ownership-masked ``psum``,
    bitwise-identical to the replicated round on shuffle sampling and
    within 2e-5 on iid; ISSUE 4.  With a ``capacity`` (ISSUE 5) each shard
    additionally COMPACTS its owned slots into a dense [capacity] lane
    block and runs only that — per-shard round compute drops from K to
    ~K/S lanes, turning the mesh into round-time speedup rather than data
    residency alone; owned slots past capacity overflow deterministically
    and are dropped like paper-style stragglers, while ``capacity=None``
    ("full") keeps the bitwise PR-4 masked mode);
  * pluggable aggregation (``repro.core.aggregation``) — who merges, how.

Three round flavours share that substrate:

  make_padded_round   the seed interface: host-stacked padded [K, max_n, ...]
                      arrays (kept for parity tests and the old-path bench)
  make_packed_round   device-resident data: the full federation lives on
                      device as one flat array + per-client offsets/lengths,
                      uploaded once; the per-round cohort gather happens on
                      device, so a round moves only O(K) ids host->device
                      instead of O(K * max_n * feature_dim) padded samples
  make_stream_round   cross-silo: a pre-batched stream of ``max_steps`` batch
                      pytrees per silo (repro.core.silo)

On top of the per-round flavours, ``make_segment_fn`` (ISSUE 3) fuses whole
MULTI-ROUND training segments into one jitted ``lax.scan``: the server-side
FedSAE logic (heterogeneity draws, Gumbel-top-k cohort selection, Ira/Fassa
workload prediction, ValueTracker refresh) runs on device via the float32
twins in repro.core.{prediction,selection,heterogeneity}, carrying
``(params, L, H, theta, values, data_rng, sel_rng)`` so zero bytes cross
the host boundary inside a block of rounds.

The model seam is the ``LocalStep`` protocol
(``repro.models.fl_models``): ``init_params(rng)`` builds an arbitrary
param PYTREE and ``loss(params, batch)`` a masked scalar; the engine
differentiates the loss and tree-maps the SGD update, so nothing here
assumes the flat ``[P]`` MCLR layout.  Every ``make_*`` entry point
coerces its ``model`` argument through ``as_local_step`` (identity for
``LocalStep``/``FLModel`` instances — the mclr fast path keeps its exact
traced functions).  At the upload boundary the client-update pytrees are
flattened to a single ``[K, P]`` vector view under the fixed-ordering
ravel contract in ``repro.core.compression`` (``flatten_global`` /
``unflatten_rows``), which is why selection, Ira/Fassa prediction, upload
compression, fault injection, the upload screen, every registry
aggregator, telemetry's byte ledger and the msgpack checkpoints work
unchanged on any model.

Every round flavour takes a ``backend`` option (``"xla"`` | ``"pallas"``,
default ``"xla"``).  ``"pallas"`` swaps the hot stages for the fused kernels
in ``repro.kernels`` — the cohort gather (``fed_gather``), the upload
compressor (``fed_compress``), and, iff the kernel-eligibility dispatch
``repro.kernels.ops.fused_sgd_eligible`` accepts the step (MCLR with
``sampling="iid"``), the budgeted local-SGD loop (``fed_local_sgd``) — and
falls back to the XLA autodiff implementation for any stage with no
applicable kernel (non-MCLR local steps, the seed-exact ``"shuffle"``
minibatch rule, silo streams), so the flag is safe to flip on every
scenario.  The platform decides how the kernels run: compiled on a TPU,
interpreted elsewhere (``repro.kernels.ops``).

Global params are donated to the round function (``donate_argnums=0``) so the
update happens in place on accelerators; donation is skipped on CPU where XLA
does not implement it (it would only emit warnings).  The backend check is
deferred to the round function's FIRST CALL, not engine or round-function
construction, so an engine built before device selection still donates
correctly.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.aggregation import Aggregator, FedAvg
from repro.models.fl_models import as_local_step
from repro.obs.profiling import (STAGE_AGGREGATE, STAGE_GATHER,
                                 STAGE_LOCAL_SGD, STAGE_UPLOAD, stage)

BACKENDS = ("xla", "pallas")
PREFETCH_MODES = ("off", "double_buffer")


def _device_hist(x, w, lo: float, hi: float, bins: int):
    """float32 fixed-bin histogram on device — the jnp twin of
    ``repro.obs.schema.histogram_counts`` (same clip/floor binning in
    float32, so host- and scan-driver telemetry land in the same bins).
    Traceable under ``lax.scan``; ``bins`` is static."""
    x = jnp.clip(jnp.asarray(x, jnp.float32), jnp.float32(lo),
                 jnp.float32(hi) - jnp.float32(hi - lo) * jnp.float32(1e-6))
    idx = jnp.floor((x - jnp.float32(lo)) / jnp.float32(hi - lo)
                    * jnp.float32(bins)).astype(jnp.int32)
    return jnp.zeros(bins, jnp.float32).at[idx].add(
        jnp.asarray(w, jnp.float32))


def _scan_prefetch(one_round, carry, ts):
    """Double-buffered block driver (ISSUE 10): run ``one_round``'s
    prepare/execute halves as  p0 (e p)* e  instead of ``lax.scan`` over
    the composed round.

    The scan carry holds cohort t's prepared bundle — selection, budgets
    and the pre-gathered training data — so each scan step EXECUTES round
    t while PREPARING round t+1 in the same XLA program region: the
    scheduler is free to overlap cohort t+1's gather DMA with cohort t's
    local-SGD compute (the payoff is on accelerators with async copies;
    on CPU the reordering is neutral).  The operation sequence
    p0 e0 p1 e1 ... is exactly the off-mode composition's, and prepare
    consumes only carry state that execute of the previous round has
    already committed (values, quarantine counters), so results are
    bit-identical to prefetch="off" (tests/test_fused_generic.py).

    Single-round blocks degenerate to a zero-length scan: prologue
    prepare + epilogue execute only."""
    prepare, execute = one_round.prepare, one_round.execute
    carry, pf = prepare(carry, ts[0])

    def body(cpf, t):
        carry, pf = cpf
        carry, stats = execute(carry, pf)
        carry, pf = prepare(carry, t)
        return (carry, pf), stats

    (carry, pf), stats = jax.lax.scan(body, (carry, pf), ts[1:])
    carry, last = execute(carry, pf)
    stats = jax.tree.map(
        lambda s, l: jnp.concatenate([s, l[None]], axis=0), stats, last)
    return carry, stats


def _check_shard_count(flat_x, mesh):
    """Trace-time guard: the packed layout's shard axis must equal the
    mesh's ``data`` axis — a divisible mismatch (e.g. a 4-shard layout on a
    2-way mesh) would pass every sharding check yet silently drop whole
    client blocks (each device keeps only ``x[0]``) and aggregate exact
    zeros for the dropped clients' cohort slots."""
    n_mesh = mesh.shape["data"]
    if flat_x.shape[0] != n_mesh:
        raise ValueError(
            f"packed layout has {flat_x.shape[0]} shards but the mesh data "
            f"axis has {n_mesh} devices; build it with packed(shards="
            f"{n_mesh})")


def budget_iters(e_eff, n, batch_size: int, max_iters: int):
    """Masked local-SGD budget from uploaded epochs (float32, traceable).

    n_iters_k = min(round(e_eff_k * ceil(n_k / B)), max_iters) — the same
    formula the host server computes in numpy, pinned to float32 so the
    scan driver and the host driver's device-rng mode agree bit-for-bit.
    """
    tau = jnp.ceil(jnp.asarray(n, jnp.float32) / jnp.float32(batch_size))
    e = jnp.asarray(e_eff, jnp.float32)
    return jnp.minimum(jnp.round(e * tau), max_iters).astype(jnp.int32)


class RoundEngine:
    """Shared executor for federated rounds with pluggable aggregation.

    Parameters
    ----------
    lr        : local-SGD learning rate
    aggregator: callable from repro.core.aggregation (default FedAvg)
    prox_mu   : proximal weight added to every local objective; defaults to
                the aggregator's own ``prox_mu`` (FedProx carries it)
    donate    : donate the global-params argument to the jitted round
    backend   : default compute backend for the round functions ("xla" |
                "pallas"); each make_* call can override it
    compress  : upload transform ("none" | "topk_q8").  With "topk_q8" the
                packed-round and segment functions take a trailing
                error-feedback residual argument and return the updated
                residual (see module docstring); "none" keeps the PR-5
                signatures and arithmetic bitwise.  Padded and stream
                rounds have no packed client axis to carry residual state
                on and reject compression.
    topk_frac : kept-coordinate fraction for "topk_q8"
                (k = ceil(topk_frac * n_params), resolved at trace time)
    faults    : optional ``repro.faults.FaultModel`` (ISSUE 8).  Corrupt
                modes that mutate uploads ("nan"/"inf"/"sign_flip"/
                "explode") add a trailing ``corrupt`` [K] bool argument to
                the packed round functions (after the residual, when
                compressing): the marked uploading rows are overwritten
                with the mode's garbage at the upload-transform seam.
                Screened modes additionally exclude the corrupt rows from
                compressed TRANSMISSION, so their error-feedback residual
                stays bit-identical to the crash-twin run.  ``None`` (and
                the pure "crash" mode) leaves every signature and traced
                program exactly as before.
    screen_norm : enable the finite/norm upload screen before aggregation
                (``repro.faults.screen_uploads``) with this delta-l2 norm
                bound.  Round functions then return a trailing ``bad``
                [K] bool output (after the residual) marking the screened
                rows.  ``None`` (default) disables the screen — the traced
                program is unchanged.
    fused_generic : fuse the generic iid local-SGD round (ISSUE 10):
                draw the whole round's minibatch indices in one randint
                (which the iid path always did), pre-gather the
                [max_iters, B, ...] batch views before the iteration scan,
                and — on the replicated scan driver — run the
                budget-compacted cohort walk (``_iid_cohort_views``): each
                iteration slot executes only the budget-sorted lane prefix
                that is actually active, skipping the masked identity
                updates that dominate under self-adaptive budgets.
                Bit-identical values to the unfused walk (the gather and
                the sort are pure data movement, skipped slots were
                identity updates; tests/test_fused_generic.py), at the
                memory cost of materializing the views (~epochs x the
                [K, max_n, ...] cohort shard).  ``False`` restores the
                per-client fetch-in-body walk.
    """

    def __init__(self, lr: float, aggregator: Optional[Aggregator] = None,
                 prox_mu: Optional[float] = None, donate: bool = True,
                 backend: str = "xla", compress: str = "none",
                 topk_frac: float = 0.1, faults=None,
                 screen_norm: Optional[float] = None,
                 fused_generic: bool = True):
        from repro.core.compression import check_compress, resolve_k

        self.lr = lr
        self.fused_generic = bool(fused_generic)
        self.aggregator = aggregator if aggregator is not None else FedAvg()
        self.prox_mu = float(prox_mu if prox_mu is not None
                             else getattr(self.aggregator, "prox_mu", 0.0))
        self.donate = donate
        self.backend = self._resolve_backend(backend)
        self.compress = check_compress(compress)
        self.topk_frac = float(topk_frac)
        resolve_k(self.topk_frac, 1)  # validate the fraction eagerly
        self.compressing = self.compress != "none"
        self.faults = faults
        self.screen_norm = None if screen_norm is None else float(screen_norm)
        self.screening = self.screen_norm is not None
        self.injecting = faults is not None and faults.injects
        # where the garbage goes in: delta-shaped modes (sign_flip,
        # explode) corrupt what the CLIENT compresses and transmits —
        # before the upload transform, as an in-line where() on the
        # trained stack.  Deriving them post-transform would collapse to
        # the global row (a non-transmitting row reconstructs to exactly
        # ``global``), and tapping the raw stack from a post-transform
        # side branch perturbs XLA's fusion of the transform enough to
        # break the crash twin's bitwise claim at the ulp level.
        # Value-independent garbage (nan/inf) corrupts the reconstructed
        # stack "on the wire" and never transmits.
        self._inject_pre = (self.injecting and self.compressing
                            and faults.corrupt in ("sign_flip", "explode"))
        self._inject_post = self.injecting and not self._inject_pre
        # a screened transmitting mode (explode) must not leak into the
        # server's error-feedback state: the residual row of a detected
        # upload keeps its pre-round bits, exactly like the crash twin's
        self._block_residual = (self._inject_pre
                                and faults.corrupt == "explode")

    # ------------------------------------------------------------------
    def _resolve_backend(self, backend: Optional[str]) -> str:
        backend = getattr(self, "backend", "xla") if backend is None \
            else backend
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; choose from {BACKENDS}")
        return backend

    def _jit_round(self, fn: Callable,
                   donate: tuple = (0,)) -> Callable:
        """Jit ``fn``, deciding donation lazily at the first call.

        ``jax.default_backend()`` must not be read while the round function
        is being built — an engine constructed before device/mesh selection
        would bake in the wrong answer.  The wrapper records its decision on
        ``.donate_argnums`` (None until the first call).

        ``donate`` is the argnum tuple to donate when donation is on —
        argnum 0 (the params/state carry) plus, for compressing round and
        segment functions, the error-feedback residual (the caller always
        reassigns both from the outputs, so the buffers are dead on entry).
        The raw body and the requested argnums stay reachable as ``._fn`` /
        ``._donate`` so the donation-audit test can compile the body with
        donation forced on and assert every donated buffer is actually
        consumed (tests/test_fused_generic.py)."""
        state: dict = {}

        def call(*args):
            jitted = state.get("jitted")
            if jitted is None:
                argnums = (tuple(donate) if self.donate
                           and jax.default_backend() != "cpu" else ())
                jitted = state["jitted"] = jax.jit(
                    fn, donate_argnums=argnums)
                call.donate_argnums = argnums
            return jitted(*args)

        call.donate_argnums = None
        call._fn = fn
        call._donate = tuple(donate)
        return call

    def _prox(self, loss, params, global_params):
        if not self.prox_mu:
            return loss
        sq = sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(global_params)))
        return loss + 0.5 * self.prox_mu * sq

    # ------------------------------------------------------------------
    # sample-level local SGD: resample batches from a padded client shard
    # ------------------------------------------------------------------
    def _iid_batch_views(self, batch_size: int, max_iters: int) -> Callable:
        """The fused iid data walk (ISSUE 10): one randint for the whole
        round's minibatch indices — the hoisted-index shape the shuffle
        path uses to dodge the XLA 0.4.x vmap-in-shard_map gather
        miscompile (see ``_local_sgd``); keep it — then ONE gather for all
        ``[max_iters, B, ...]`` batch views.

        prep(fetch, nk, key) -> (xb_all [max_iters, B, ...], yb_all
        [max_iters, B], bmask [B]) — ``fetch`` is the same closure the
        unfused walk uses (gathers broadcast over the extra leading index
        axis), so the views hold bit-identical values to the per-iteration
        fetches."""
        B = batch_size

        def prep(fetch, nk, key):
            nk_safe = jnp.maximum(nk, 1)
            idx_all = jax.random.randint(key, (max_iters, B), 0, nk_safe)
            xb_all, yb_all = fetch(idx_all)
            bmask = (jnp.arange(B) < nk_safe).astype(jnp.float32)
            return xb_all, yb_all, bmask

        return prep

    def _iid_scan_views(self, model, batch_size: int,
                        max_iters: int) -> Callable:
        """The compute half of the fused iid walk: scan all ``max_iters``
        budget slots over pre-gathered batch views — the loop body is pure
        autodiff + masked update, no gather dispatch.

        run(global_params, xb_all, yb_all, bmask, iters) ->
            (params, mean_loss)"""
        lr = self.lr

        def run(global_params, xb_all, yb_all, bmask, iters):
            def step(params, xs):
                i, xb, yb = xs
                batch = {"x": xb, "y": yb, "mask": bmask}

                def loss_fn(p):
                    return self._prox(model.loss(p, batch), p, global_params)

                loss, g = jax.value_and_grad(loss_fn)(params)
                active = (i < iters).astype(jnp.float32)
                return jax.tree.map(lambda p, gg: p - lr * active * gg,
                                    params, g), loss

            params, losses = jax.lax.scan(
                step, global_params,
                (jnp.arange(max_iters), xb_all, yb_all))
            msk = (jnp.arange(max_iters) < iters).astype(jnp.float32)
            return params, (losses * msk).sum() / jnp.maximum(msk.sum(), 1)

        return run

    def _iid_cohort_views(self, model, batch_size: int, max_iters: int):
        """Budget-compacted cohort local SGD over pre-gathered batch views
        — the fused generic driver's compute half (ISSUE 10).

        ``jax.vmap(_iid_scan_views)`` executes every ``max_iters`` slot on
        every cohort lane and discards the masked work (``active=0`` slots
        are identity updates).  Under FedSAE's self-adaptive budgets most
        (lane, slot) pairs ARE masked — small-workload clients get 0-1 of
        the straggler-sized ``max_iters`` slots — so the masked walk burns
        the majority of local-SGD compute on identity updates.  This
        runner skips them:

        - lanes are stable-sorted by descending budget, so slot ``i``'s
          active lanes form a PREFIX of the lane axis;
        - each slot dispatches (``lax.switch``) to the smallest
          power-of-two prefix >= its active-lane count and runs the
          vmapped step on that static slice only;
        - results are scattered back through the inverse permutation.

        Bitwise-identical to the unfused walk by construction: executed
        (lane, slot) pairs run literally the same per-lane step (padding
        lanes inside a prefix keep their ``active=0`` masking, so they
        stay identity updates), skipped pairs were identity updates whose
        losses the per-lane mean already masked out, and the sort is pure
        data movement inverted on the way out
        (tests/test_fused_generic.py pins this against the per-lane walk
        across drivers and models)."""
        lr = self.lr

        def lane_step(global_params, params, xb, yb, bm, active):
            # the unfused walk's loop body, verbatim (bitwise contract)
            batch = {"x": xb, "y": yb, "mask": bm}

            def loss_fn(p):
                return self._prox(model.loss(p, batch), p, global_params)

            loss, g = jax.value_and_grad(loss_fn)(params)
            return jax.tree.map(lambda p, gg: p - lr * active * gg,
                                params, g), loss

        def run_cohort(global_params, xb_all, yb_all, bmask, iters):
            K = iters.shape[0]
            sizes = [0]
            s = 1
            while s < K:
                sizes.append(s)
                s *= 2
            sizes.append(K)

            order = jnp.argsort(-iters)        # stable: prefix per slot
            inv = jnp.argsort(order)           # inverse permutation
            xb_s = jnp.swapaxes(xb_all[order], 0, 1)   # [IT, K, B, ...]
            yb_s = jnp.swapaxes(yb_all[order], 0, 1)
            bm_s = bmask[order]
            it_s = iters[order]
            slot = jnp.arange(max_iters)
            counts = (slot[:, None] < it_s[None, :]).sum(1)      # [IT]
            bidx = jnp.searchsorted(jnp.asarray(sizes), counts)
            params0 = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (K,) + l.shape),
                global_params)

            def make_branch(S):
                if S == 0:
                    def branch(op):
                        return op[0], jnp.zeros((K,), jnp.float32)
                    return branch

                def branch(op):
                    params, xb_i, yb_i, active = op

                    def cut(t):
                        return t[:S]

                    p_s, loss_s = jax.vmap(
                        lane_step, in_axes=(None, 0, 0, 0, 0, 0))(
                        global_params, jax.tree.map(cut, params),
                        cut(xb_i), cut(yb_i), cut(bm_s), cut(active))
                    new_params = jax.tree.map(
                        lambda full, upd: full.at[:S].set(upd),
                        params, p_s)
                    return new_params, jnp.zeros(
                        (K,), jnp.float32).at[:S].set(loss_s)

                return branch

            branches = [make_branch(S) for S in sizes]

            def step(params, xs):
                i, b, xb_i, yb_i = xs
                active = (i < it_s).astype(jnp.float32)
                return jax.lax.switch(b, branches,
                                      (params, xb_i, yb_i, active))

            params_s, losses_s = jax.lax.scan(
                step, params0, (slot, bidx, xb_s, yb_s))
            msk = (slot[:, None] < it_s[None, :]).astype(jnp.float32)
            mean = (losses_s * msk).sum(0) / jnp.maximum(msk.sum(0), 1)
            return (jax.tree.map(lambda t: t[inv], params_s), mean[inv])

        return run_cohort

    def _iid_sgd_core(self, model, batch_size: int, max_iters: int,
                      fused: Optional[bool] = None):
        """The iid minibatch loop, parameterized over the batch fetch.

        One implementation serves both data layouts — the gathered
        [max_n, ...] client shard (``fetch = lambda idx: (xk[idx],
        yk[idx])``) and direct packed indexing (``fetch = lambda idx:
        (flat_x[off_k + idx], ...)``) — so the two paths stay bit-identical
        by construction: same randint draw, same masks, same update and
        loss-mean arithmetic (the contract tests/test_scan_driver.py
        asserts).

        One threefry call for the whole round instead of a
        fold_in+randint per iteration; idx < nk always lands on a real
        sample (both stacked() and the packed layout are
        real-samples-first), so no validity-mask gather is needed.  The
        reported loss is the mean minibatch loss over executed iterations
        (silo-round semantics): no extra full-shard pass.  Zero-budget
        clients report 0.0; the server never consumes losses of
        non-uploaders.

        ``fused`` (default: the engine's ``fused_generic``) picks the data
        walk: the fused one pre-gathers every batch view before the scan
        (``_iid_batch_views`` + ``_iid_scan_views``) so generic LocalStep
        bodies stop paying a per-iteration gather; the unfused one fetches
        inside the loop body.  Both walks produce bit-identical results —
        the gather is pure data movement (tests/test_fused_generic.py).
        """
        fused = self.fused_generic if fused is None else bool(fused)
        lr = self.lr
        B = batch_size

        if fused:
            prep = self._iid_batch_views(batch_size, max_iters)
            run = self._iid_scan_views(model, batch_size, max_iters)

            def train(global_params, fetch, nk, iters, key):
                xb_all, yb_all, bmask = prep(fetch, nk, key)
                return run(global_params, xb_all, yb_all, bmask, iters)

            return train

        def train(global_params, fetch, nk, iters, key):
            nk_safe = jnp.maximum(nk, 1)
            idx_all = jax.random.randint(key, (max_iters, B), 0, nk_safe)
            bmask = (jnp.arange(B) < nk_safe).astype(jnp.float32)

            def step(params, xs):
                i, idx = xs
                xb, yb = fetch(idx)
                batch = {"x": xb, "y": yb, "mask": bmask}

                def loss_fn(p):
                    return self._prox(model.loss(p, batch), p, global_params)

                loss, g = jax.value_and_grad(loss_fn)(params)
                active = (i < iters).astype(jnp.float32)
                return jax.tree.map(lambda p, gg: p - lr * active * gg,
                                    params, g), loss

            params, losses = jax.lax.scan(
                step, global_params, (jnp.arange(max_iters), idx_all))
            msk = (jnp.arange(max_iters) < iters).astype(jnp.float32)
            return params, (losses * msk).sum() / jnp.maximum(msk.sum(), 1)

        return train

    def _local_sgd(self, model, batch_size: int, max_iters: int,
                   sampling: str = "shuffle"):
        """``sampling`` picks the minibatch rule:

        shuffle  the seed semantics — one random epoch permutation per round,
                 batches walk it modulo n_k, and the reported client loss is
                 a dedicated post-training pass over the full local shard.
                 Bit-identical to the pre-refactor round, but the vmapped
                 argsort costs as much as the whole restack it replaced
                 (XLA CPU sort is slow).
        iid      per-iteration uniform minibatches with replacement
                 (standard SGD, ``_iid_sgd_core`` on the gathered shard).
        """
        if sampling not in ("shuffle", "iid"):
            raise ValueError(f"unknown sampling {sampling!r}")
        lr = self.lr
        B = batch_size

        if sampling == "iid":
            core = self._iid_sgd_core(model, batch_size, max_iters)

            def local_train(global_params, xk, yk, maskk, nk, iters, key):
                return core(global_params, lambda idx: (xk[idx], yk[idx]),
                            nk, iters, key)

            return local_train

        def local_train(global_params, xk, yk, maskk, nk, iters, key):
            M = xk.shape[0]
            nk_safe = jnp.maximum(nk, 1)
            perm = jnp.argsort(jax.random.uniform(key, (M,))
                               + (1.0 - maskk) * 1e9)
            # The epoch walk perm[(i*B + arange(B)) % nk] for all steps at
            # once, scanned as xs.  Bit-identical indices to gathering perm
            # inside the loop body, but hoisted because XLA 0.4.x CPU
            # MISCOMPILES a loop-variant dynamic gather of perm under
            # vmap-inside-shard_map (the sharded path, ISSUE 4) — the iid
            # path's precomputed idx_all never hit this.
            idx_all = perm[jnp.arange(max_iters * B).reshape(max_iters, B)
                           % nk_safe]

            def step(params, xs):
                i, idx = xs
                batch = {"x": xk[idx], "y": yk[idx],
                         "mask": maskk[idx] * (jnp.arange(B) < nk_safe)}

                def loss_fn(p):
                    return self._prox(model.loss(p, batch), p, global_params)

                _, g = jax.value_and_grad(loss_fn)(params)
                active = (i < iters).astype(jnp.float32)
                return jax.tree.map(lambda p, gg: p - lr * active * gg,
                                    params, g), None

            params, _ = jax.lax.scan(step, global_params,
                                     (jnp.arange(max_iters), idx_all))
            # seed semantics: post-training loss over the full shard
            final_loss = model.loss(params, {"x": xk, "y": yk, "mask": maskk})
            return params, final_loss

        return local_train

    @staticmethod
    def _upload_weights(n, n_iters):
        """Aggregation weights from sample counts and budgets: a client
        contributes its sample count iff it trained at least one step."""
        return n.astype(jnp.float32) * (n_iters > 0).astype(jnp.float32)

    def _finish(self, global_params, params_k, weights):
        """Stage 4: screen (optional) + aggregate.

        ``weights`` is the [K] f32 aggregation-weight vector (0 = no
        upload) — packed rounds build it with :meth:`_upload_weights`, the
        cross-silo stream round passes its caller-supplied weights, so
        every flavour finishes through this one seam.

        Returns ``(new_global, uploaded_any, bad)`` where ``bad`` is the
        [K] bool mask of screen-rejected rows (all-False zeros when the
        screen is off — callers only propagate it when
        ``self.screening``).  A screened row is demoted to the zero-budget
        crash branch before the aggregator ever sees it: weight 0 AND the
        global-params row value, so no registry aggregator — weighted mean
        or distance-based — can be poisoned by it, and an all-faulty round
        degenerates to the existing no-participant no-op."""
        with stage(STAGE_AGGREGATE):
            if self.screening:
                from repro.faults.screen import screen_uploads
                params_k, weights, bad = screen_uploads(
                    global_params, params_k, weights, self.screen_norm)
                # fence the sanitized stack: the injection dataflow differs
                # between a faulted run and its crash twin, and letting XLA
                # fuse the aggregator with either upstream graph perturbs
                # the reduction at the ulp level — behind the barrier both
                # programs aggregate bitwise-identical inputs identically
                params_k, weights = jax.lax.optimization_barrier(
                    (params_k, weights))
            else:
                bad = jnp.zeros(weights.shape, bool)
            new_global = self.aggregator(params_k, global_params, weights)
            return new_global, weights.sum() > 0, bad

    def _inject_faults(self, global_params, params_k, corrupt, uploading):
        """Overwrite the ``corrupt & uploading`` rows of the stacked upload
        with the configured garbage (``repro.faults.inject``).  Rows that
        uploaded nothing are never corrupted — they carry the exact
        crash-branch value and weight 0, so injecting into them would dodge
        the weight-gated screen and poison distance-based aggregators.

        The injection is a pure in-line ``where()`` on the stack it
        corrupts (pre-transform for delta-shaped modes, post-reconstruction
        for nan/inf — see ``_inject_pre``); it never taps another tensor
        from a side branch, which is what keeps the faulted program's
        fusion — and therefore the non-corrupt rows' bits — identical to
        the crash twin's."""
        from repro.faults.inject import inject_upload_faults
        fm = self.faults
        mask = corrupt & uploading
        with stage(STAGE_UPLOAD):
            return inject_upload_faults(params_k, global_params, mask,
                                        fm.corrupt, fm.explode_factor)

    def _upload_transform(self, global_params, params_k, residual_rows,
                          uploaded, backend: str):
        """Stage 3 of the round pipeline (see module docstring): compress
        the trained stack's deltas against ``residual_rows`` [rows, P] and
        dense-reconstruct.  ``uploaded`` rows transmit; the rest
        reconstruct to exactly ``global`` and keep their residual
        bit-unchanged.  k is static, resolved from the pytree at trace
        time."""
        from repro.core import compression as comp
        with stage(STAGE_UPLOAD):
            k = comp.resolve_k(self.topk_frac,
                               comp.n_params_of(global_params))
            rec, new_rows, _ = comp.apply_upload_compress(
                global_params, params_k, residual_rows, uploaded, k, backend)
            return rec, new_rows

    def _finish_round(self, global_params, params_k, losses, n, n_iters,
                      backend: str, residual=None, ids=None, corrupt=None):
        """Stages 3+4 for every replicated packed round body: optional
        fault injection at the upload seam, the upload transform with
        error feedback, then screen + aggregate.  Shared verbatim by the
        gather-based body, the direct-iid body and the prefetch execute
        half, so their traced post-training programs are identical by
        construction.  Returns the body's output tuple: (new_global,
        losses, any_up[, residual][, bad])."""
        injecting, screening = self.injecting, self.screening
        if self.compressing:
            uploading = n_iters > 0
            transmit = uploading
            if self._inject_pre:      # sign_flip/explode: the client
                params_k = self._inject_faults(  # transmits the garbage
                    global_params, params_k, corrupt, uploading)
            elif injecting:           # nan/inf garbage never transmits
                transmit = uploading & ~corrupt
            params_k, new_rows = self._upload_transform(
                global_params, params_k, residual[ids], transmit,
                backend)
            if self._block_residual:  # screened transmit (explode):
                # the error-feedback rows of detected uploads keep
                # their pre-round bits (crash-twin residual parity)
                residual = residual.at[
                    jnp.where(corrupt, residual.shape[0], ids)].set(
                    new_rows, mode="drop")
            else:
                residual = residual.at[ids].set(new_rows)  # distinct
            if self._inject_post:
                params_k = self._inject_faults(global_params, params_k,
                                               corrupt, uploading)
            new_global, any_up, bad = self._finish(
                global_params, params_k,
                self._upload_weights(n, n_iters))
            if screening:
                return new_global, losses, any_up, residual, bad
            return new_global, losses, any_up, residual
        if injecting:
            params_k = self._inject_faults(global_params, params_k,
                                           corrupt, n_iters > 0)
        new_global, any_up, bad = self._finish(
            global_params, params_k, self._upload_weights(n, n_iters))
        if screening:
            return new_global, losses, any_up, bad
        return new_global, losses, any_up

    # ------------------------------------------------------------------
    # pallas-backend stages (repro.kernels); each falls back to the XLA
    # implementation when no kernel applies
    # ------------------------------------------------------------------
    def _can_fuse_sgd(self, model, sampling: str) -> bool:
        """Kernel-eligibility dispatch lives with the kernels
        (``repro.kernels.ops.fused_sgd_eligible``): fused local-SGD
        kernels cover MCLR and dense-MLP steps with iid minibatches; every
        other ``LocalStep`` keeps the XLA autodiff scan."""
        from repro.kernels.ops import fused_sgd_eligible
        return fused_sgd_eligible(model, sampling)

    def _fused_sgd(self, model, global_params, x, y, n, n_iters, keys,
                   batch_size: int, max_iters: int):
        """Budgeted local SGD through the fused kernel for ``model.kind``
        (fed_local_sgd for MCLR, fed_local_sgd_dense for the two-layer MLP
        family — dispatch, not assumption).  Minibatch indices are drawn
        with the exact randint call the XLA iid path uses, so the backends
        see bit-identical batches."""
        from repro.kernels import ops as kops
        idx = jax.vmap(lambda key, nk: jax.random.randint(
            key, (max_iters, batch_size), 0, jnp.maximum(nk, 1)))(keys, n)
        kind = getattr(model, "kind", None)
        if kind == "mlp":
            w1_k, b1_k, w2_k, b2_k, losses = kops.fed_local_sgd_dense(
                x, y, idx, global_params["w1"], global_params["b1"],
                global_params["w2"], global_params["b2"],
                n.astype(jnp.int32), n_iters.astype(jnp.int32),
                lr=self.lr, prox_mu=self.prox_mu)
            return {"w1": w1_k, "b1": b1_k, "w2": w2_k, "b2": b2_k}, losses
        if kind != "mclr":
            raise ValueError(
                f"no fused local-SGD kernel for step kind {kind!r} "
                "(fused_sgd_eligible should have dispatched it to the "
                "XLA path)")
        w_k, b_k, losses = kops.fed_local_sgd_mclr(
            x, y, idx, global_params["w"], global_params["b"],
            n.astype(jnp.int32), n_iters.astype(jnp.int32),
            lr=self.lr, prox_mu=self.prox_mu)
        return {"w": w_k, "b": b_k}, losses

    # ------------------------------------------------------------------
    def make_padded_round(self, model, batch_size: int, max_iters: int,
                          sampling: str = "shuffle",
                          backend: Optional[str] = None) -> Callable:
        """Seed-interface round over host-stacked padded arrays.

        round_fn(global_params, x, y, mask, n, n_iters, rng) ->
            (new_global_params, client_losses, uploaded_any)
          x: [K, max_n, ...] padded client data;  mask: [K, max_n]
          n: [K] true sample counts;  n_iters: [K] masked local-SGD budget
        """
        if self.compressing:
            raise ValueError(
                "upload compression needs the packed client axis for "
                "residual state; the padded seed round does not support "
                "it — use make_packed_round/make_segment_fn")
        if self.injecting or self.screening:
            raise ValueError(
                "fault injection / upload screening are packed-round "
                "features; the padded seed round does not support them — "
                "use make_packed_round/make_segment_fn")
        model = as_local_step(model)
        backend = self._resolve_backend(backend)
        fuse_sgd = backend == "pallas" and self._can_fuse_sgd(model, sampling)
        local_train = None if fuse_sgd else \
            self._local_sgd(model, batch_size, max_iters, sampling)

        def round_fn(global_params, x, y, mask, n, n_iters, rng):
            keys = jax.random.split(rng, x.shape[0])
            if fuse_sgd:
                params_k, losses = self._fused_sgd(
                    model, global_params, x, y, n, n_iters, keys,
                    batch_size, max_iters)
            else:
                params_k, losses = jax.vmap(
                    local_train, in_axes=(None, 0, 0, 0, 0, 0, 0))(
                    global_params, x, y, mask, n, n_iters, keys)
            new_global, any_up, _ = self._finish(
                global_params, params_k, self._upload_weights(n, n_iters))
            return new_global, losses, any_up

        return self._jit_round(round_fn)

    # ------------------------------------------------------------------
    @staticmethod
    def _cohort_gather(max_n: int, backend: str) -> Callable:
        """gather(flat_x, flat_y, offs [K], n [K]) -> (x [K, max_n, ...],
        y [K, max_n], mask [K, max_n]) — XLA clamp-gather or the pallas
        fed_gather kernel.  Works on the global flat arrays and on a
        shard-local slice alike (both honour the max_n tail-slack
        contract)."""
        if backend == "pallas":
            def gather(flat_x, flat_y, offs, n):
                from repro.kernels import ops as kops
                return kops.fed_cohort_gather(flat_x, flat_y, offs, n, max_n)
            return gather

        def gather(flat_x, flat_y, offs, n):
            total = flat_x.shape[0]
            pos = jnp.arange(max_n)
            idx = jnp.minimum(offs[:, None] + pos[None, :], total - 1)
            mask = (pos[None, :] < n[:, None]).astype(jnp.float32)
            return flat_x[idx], flat_y[idx], mask
        return gather

    def _packed_round_body(self, model, batch_size: int, max_iters: int,
                           max_n: int, sampling: str = "shuffle",
                           backend: Optional[str] = None) -> Callable:
        """Un-jitted packed-round body — shared by :meth:`make_packed_round`
        (which jits it standalone) and :meth:`make_segment_fn` (which traces
        it inside the multi-round ``lax.scan``).

        With ``compress="topk_q8"`` the round function takes a trailing
        ``residual`` [N, P] argument (full-federation error-feedback state,
        rows indexed by client id) and returns it updated as a fourth
        output; cohort rows with ``n_iters > 0`` go through the upload
        transform, all other rows stay bit-unchanged.

        Fault threading (ISSUE 8, all statically gated — see the engine
        constructor): with an injecting FaultModel the round function takes
        a trailing ``corrupt`` [K] bool argument; with the screen on it
        returns a trailing ``bad`` [K] bool output.  Screened corrupt rows
        are excluded from compressed transmission (their residual rows stay
        bit-identical to the crash-twin run) and the post-transform stack
        is corrupted "on the wire" instead."""
        model = as_local_step(model)
        backend = self._resolve_backend(backend)
        fuse_sgd = backend == "pallas" and self._can_fuse_sgd(model, sampling)
        local_train = None if fuse_sgd else \
            self._local_sgd(model, batch_size, max_iters, sampling)
        gather = self._cohort_gather(max_n, backend)

        def train_cohort(global_params, flat_x, flat_y, offsets, lengths,
                         ids, n_iters, rng):
            with stage(STAGE_GATHER):
                offs = offsets[ids]
                n = jnp.minimum(lengths[ids], max_n)
                x, y, mask = gather(flat_x, flat_y, offs, n)
            with stage(STAGE_LOCAL_SGD):
                keys = jax.random.split(rng, ids.shape[0])
                if fuse_sgd:
                    params_k, losses = self._fused_sgd(
                        model, global_params, x, y, n, n_iters, keys,
                        batch_size, max_iters)
                else:
                    params_k, losses = jax.vmap(
                        local_train, in_axes=(None, 0, 0, 0, 0, 0, 0))(
                        global_params, x, y, mask, n, n_iters, keys)
            return params_k, losses, n

        if self.compressing:
            def round_fn(global_params, flat_x, flat_y, offsets, lengths,
                         ids, n_iters, rng, residual, corrupt=None):
                params_k, losses, n = train_cohort(
                    global_params, flat_x, flat_y, offsets, lengths, ids,
                    n_iters, rng)
                return self._finish_round(
                    global_params, params_k, losses, n, n_iters, backend,
                    residual=residual, ids=ids, corrupt=corrupt)

            return round_fn

        def round_fn(global_params, flat_x, flat_y, offsets, lengths, ids,
                     n_iters, rng, corrupt=None):
            params_k, losses, n = train_cohort(
                global_params, flat_x, flat_y, offsets, lengths, ids,
                n_iters, rng)
            return self._finish_round(global_params, params_k, losses, n,
                                      n_iters, backend, corrupt=corrupt)

        return round_fn

    def _direct_iid_round_body(self, model, batch_size: int, max_iters: int,
                               max_n: int,
                               fused: Optional[bool] = None) -> Callable:
        """Gather-free iid round: minibatches are indexed straight out of
        the packed flat arrays (``flat_x[offset_k + idx]``), so the
        [K, max_n, feat] cohort shard is never materialized.

        Bit-identical to the gather-based iid path — same randint draws,
        and ``x_k[idx] == flat_x[offset_k + idx]`` for every idx < n_k
        (clients are laid out real-samples-first) — but it reads O(iters *
        B * feat) instead of writing an O(K * max_n * feat) intermediate,
        which is what lets the scan driver clear 2x at paper scale.

        ``fused`` (default: the engine's ``fused_generic``) picks the
        local-SGD walk: the fused one pre-gathers all batch views and runs
        the budget-compacted cohort scan (``_iid_cohort_views`` — masked
        budget slots are skipped, not executed-and-discarded); the unfused
        one is the per-client per-iteration fetch loop.  Bit-identical
        either way (tests/test_fused_generic.py).
        """
        fused = self.fused_generic if fused is None else bool(fused)
        step_model = as_local_step(model)
        if fused:
            prep = self._iid_batch_views(batch_size, max_iters)
            run_cohort = self._iid_cohort_views(step_model, batch_size,
                                                max_iters)

            def train_cohort(global_params, flat_x, flat_y, offsets,
                             lengths, ids, n_iters, rng):
                with stage(STAGE_GATHER):
                    offs = offsets[ids]
                    n = jnp.minimum(lengths[ids], max_n)
                    keys = jax.random.split(rng, ids.shape[0])

                    def one(off_k, nk, key):
                        return prep(lambda idx: (flat_x[off_k + idx],
                                                 flat_y[off_k + idx]),
                                    nk, key)

                    xb, yb, bm = jax.vmap(one)(offs, n, keys)
                with stage(STAGE_LOCAL_SGD):
                    params_k, losses = run_cohort(global_params, xb, yb,
                                                  bm, n_iters)
                return params_k, losses, n
        else:
            core = self._iid_sgd_core(step_model, batch_size, max_iters,
                                      fused=False)

            def train_cohort(global_params, flat_x, flat_y, offsets,
                             lengths, ids, n_iters, rng):
                with stage(STAGE_GATHER):
                    # direct packed indexing: the "gather" stage reduces to
                    # the per-client offset/length lookup (no cohort shard
                    # is built)
                    offs = offsets[ids]
                    n = jnp.minimum(lengths[ids], max_n)
                with stage(STAGE_LOCAL_SGD):
                    keys = jax.random.split(rng, ids.shape[0])

                    def local_train(off_k, nk, iters, key):
                        return core(global_params,
                                    lambda idx: (flat_x[off_k + idx],
                                                 flat_y[off_k + idx]),
                                    nk, iters, key)

                    params_k, losses = jax.vmap(local_train)(offs, n,
                                                             n_iters, keys)
                return params_k, losses, n

        if self.compressing:
            def round_fn(global_params, flat_x, flat_y, offsets, lengths,
                         ids, n_iters, rng, residual, corrupt=None):
                params_k, losses, n = train_cohort(
                    global_params, flat_x, flat_y, offsets, lengths, ids,
                    n_iters, rng)
                return self._finish_round(
                    global_params, params_k, losses, n, n_iters, "xla",
                    residual=residual, ids=ids, corrupt=corrupt)

            return round_fn

        def round_fn(global_params, flat_x, flat_y, offsets, lengths, ids,
                     n_iters, rng, corrupt=None):
            params_k, losses, n = train_cohort(
                global_params, flat_x, flat_y, offsets, lengths, ids,
                n_iters, rng)
            return self._finish_round(global_params, params_k, losses, n,
                                      n_iters, "xla", corrupt=corrupt)

        return round_fn

    def _prefetched_round_parts(self, model, batch_size: int,
                                max_iters: int, max_n: int, sampling: str,
                                backend: Optional[str] = None):
        """The training stage of a packed round, split at the data seam
        for the double-buffered segment (ISSUE 10):

            prep_data(flat_x, flat_y, offsets, lengths, ids, sub) -> data
            train_data(global_params, data, n_iters, sub)
                -> (params_k, losses, n)

        ``prep_data`` runs in the round's PREPARE half (prefetched one
        round ahead); ``train_data`` in EXECUTE.  Together they compute
        bitwise what the off-mode bodies' train_cohort computes — same
        randint draws (same ``sub``), same gathers, same scan arithmetic;
        only the trace placement moves (tests/test_fused_generic.py).

        Dispatch mirrors the off-mode segment: backend="xla" + iid
        prepares the per-client ``[max_iters, B, ...]`` minibatch views
        straight out of the packed arrays (prefetching IS the hoisted
        fused data walk, so ``fused_generic=False`` never reaches here);
        any other sampling/backend pre-gathers the [K, max_n, ...] cohort
        shard and executes the usual fused-kernel or autodiff local SGD
        on it."""
        model = as_local_step(model)
        backend = self._resolve_backend(backend)

        if backend == "xla" and sampling == "iid":
            prep = self._iid_batch_views(batch_size, max_iters)
            run_cohort = self._iid_cohort_views(model, batch_size,
                                                max_iters)

            def prep_data(flat_x, flat_y, offsets, lengths, ids, sub):
                with stage(STAGE_GATHER):
                    offs = offsets[ids]
                    n = jnp.minimum(lengths[ids], max_n)
                    keys = jax.random.split(sub, ids.shape[0])

                    def one(off_k, nk, key):
                        return prep(lambda idx: (flat_x[off_k + idx],
                                                 flat_y[off_k + idx]),
                                    nk, key)

                    xb, yb, bm = jax.vmap(one)(offs, n, keys)
                return {"xb": xb, "yb": yb, "bmask": bm, "n": n}

            def train_data(global_params, data, n_iters, sub):
                with stage(STAGE_LOCAL_SGD):
                    params_k, losses = run_cohort(
                        global_params, data["xb"], data["yb"],
                        data["bmask"], n_iters)
                return params_k, losses, data["n"]

            return prep_data, train_data

        gather = self._cohort_gather(max_n, backend)
        fuse_sgd = backend == "pallas" and self._can_fuse_sgd(model,
                                                              sampling)
        local_train = None if fuse_sgd else \
            self._local_sgd(model, batch_size, max_iters, sampling)

        def prep_data(flat_x, flat_y, offsets, lengths, ids, sub):
            with stage(STAGE_GATHER):
                offs = offsets[ids]
                n = jnp.minimum(lengths[ids], max_n)
                x, y, mask = gather(flat_x, flat_y, offs, n)
            return {"x": x, "y": y, "mask": mask, "n": n}

        def train_data(global_params, data, n_iters, sub):
            n = data["n"]
            with stage(STAGE_LOCAL_SGD):
                keys = jax.random.split(sub, n.shape[0])
                if fuse_sgd:
                    params_k, losses = self._fused_sgd(
                        model, global_params, data["x"], data["y"], n,
                        n_iters, keys, batch_size, max_iters)
                else:
                    params_k, losses = jax.vmap(
                        local_train, in_axes=(None, 0, 0, 0, 0, 0, 0))(
                        global_params, data["x"], data["y"], data["mask"],
                        n, n_iters, keys)
            return params_k, losses, n

        return prep_data, train_data

    def make_packed_round(self, model, batch_size: int, max_iters: int,
                          max_n: int, sampling: str = "shuffle",
                          backend: Optional[str] = None,
                          mesh=None, capacity: Optional[int] = None
                          ) -> Callable:
        """Device-resident round: cohort gather from packed client data.

        round_fn(global_params, flat_x, flat_y, offsets, lengths, ids,
                 n_iters, rng) -> (new_global_params, client_losses,
                 uploaded_any)

        With ``compress="topk_q8"`` (engine option) the round function
        takes a trailing error-feedback ``residual`` argument and returns
        the updated residual as a fourth output — [N, P] replicated, or
        [S, C, P] sharded with the client axis when ``mesh`` is given (see
        module docstring; allocate with
        :func:`repro.core.compression.n_params_of` zeros).

        ``flat_x/flat_y/offsets/lengths`` are the once-uploaded packed
        federation (repro.data.federated.PackedClients); ``ids`` is the [K]
        cohort.  The [K, max_n, ...] shards are gathered on device.  Padding
        rows carry neighbouring clients' samples (XLA clamp-gather) or the
        DMA window tail (pallas fed_gather kernel) rather than zeros — they
        are masked out of every loss and never enter batch sampling, so with
        ``sampling="shuffle"`` BOTH backends are bit-identical to the padded
        path (proved by tests/test_engine.py and tests/test_fed_kernels.py).

        ``mesh`` (ISSUE 4): a 1-D ``data`` mesh.  The packed arrays must
        then carry the sharded [S, ...] layout (``packed(shards=S)``); the
        gather + budgeted local SGD run under ``shard_map`` with each shard
        training only the cohort slots it owns (see
        :meth:`_sharded_round_fn`).  Bitwise-identical to the replicated
        round on shuffle sampling; within 2e-5 on iid (observed bitwise,
        only the tolerance is guaranteed — tests/test_sharding.py).

        ``capacity`` (ISSUE 5, sharded only — a resolved per-shard lane
        count from ``repro.core.selection.resolve_capacity``, or None for
        the masked full-K mode): each shard compacts its owned cohort
        slots into a dense [capacity] block and runs only that; owned
        slots past capacity overflow deterministically (slot-index order)
        and are dropped with zero budget/weight.  Any ``capacity >= max
        owned slots per shard`` is bitwise the masked mode
        (tests/test_capacity.py).
        """
        donate = (0, 8) if self.compressing else (0,)
        if mesh is not None:
            return self._jit_round(self._sharded_round_fn(
                model, batch_size, max_iters, max_n, sampling, backend,
                mesh, capacity), donate=donate)
        if capacity is not None:
            raise ValueError(
                "capacity compaction requires a sharded mesh; pass mesh= "
                "or leave capacity=None for the replicated round")
        return self._jit_round(self._packed_round_body(
            model, batch_size, max_iters, max_n, sampling, backend),
            donate=donate)

    # ------------------------------------------------------------------
    # sharded rounds (ISSUE 4): the client axis lives on the `data` mesh
    # ------------------------------------------------------------------
    def _shard_round_core(self, model, batch_size: int, max_iters: int,
                          max_n: int, sampling: str = "shuffle",
                          backend: Optional[str] = None,
                          capacity: Optional[int] = None) -> Callable:
        """Per-shard cohort compute; must run inside ``shard_map`` over the
        ``data`` axis.

        core(global_params, flat_x, flat_y, offsets, lengths, ids, n_iters,
             rng) -> (params_k [K, ...], losses [K])   — both replicated

        With ``compress="topk_q8"`` the core takes a trailing ``residual``
        [C, P] argument — the SHARD-LOCAL error-feedback rows for the C
        clients this shard owns — and returns it updated as a third
        output.  Each executing lane reads the residual row of the client
        it serves (through ``local``), runs the upload transform on its
        delta, and scatters the updated row back; lanes that transmit
        nothing (non-owned slots in masked mode, sentinel lanes under
        capacity, zero-budget clients, and — because no lane serves them —
        capacity-overflowed slots) leave their rows bit-unchanged.  The
        scatter uses a C-sentinel row index with ``mode="drop"``: cohort
        ids are distinct, so writing lanes never collide.  The psum-rebuilt
        stack then carries the dense RECONSTRUCTION (``global + q *
        scale``) in uploading slots and exact zeros elsewhere, exactly like
        the uncompressed ownership-masked rebuild.

        Arguments are the SHARD-LOCAL packed arrays (leading shard axis
        already stripped); ``ids``/``n_iters``/``rng`` are replicated.  Each
        shard resolves which cohort slots it owns (``ids // C ==
        axis_index``), gathers and trains ONLY from its local flat arrays,
        then the [K] stacks are rebuilt with an ownership-masked ``psum``:
        every slot is computed by at most one shard and all other shards
        contribute exact zeros, so the reduction is bitwise the replicated
        stack — and arbitrary aggregators (median, Krum, ...) stay
        pluggable because they still see the full per-client stack.

        ``capacity`` (ISSUE 5) picks how the owned slots execute:

          None       masked full-K mode — every shard runs all K lanes with
                     non-owned budgets zeroed.  Bitwise the PR-4 round;
                     data residency only, no compute scaling.
          int        capacity-compacted mode — the shard packs its owned
                     slots into a dense ``[capacity]`` lane block
                     (``compact_lane_map``) and runs ONLY that block, so
                     per-shard round compute drops from K lanes to
                     ``capacity`` (~K/S) lanes; lane results scatter back
                     to their global [K] slots before the psum.  Each lane
                     reuses the key/budget/data of the slot it serves, so
                     any ``capacity >= max owned slots per shard`` is
                     bitwise the masked mode.  Owned slots past capacity
                     OVERFLOW (slot-index order, ``cohort_overflow``): no
                     lane executes them, their stack rows stay exact zeros
                     and their budgets were already zeroed by the caller,
                     so aggregation treats them like paper-style dropped
                     stragglers (weight 0 — validity masking keeps every
                     aggregator correct).

        All three compute paths mirror their replicated twins so parity is
        by construction: pallas fused SGD, XLA direct-iid packed indexing,
        and the gather + vmapped local-SGD scan (either gather backend).
        The pallas kernels need no capacity variant: their grid is the
        leading cohort-block axis, so compacted [capacity]-sized inputs
        give capacity-sized grids for free.
        """
        from repro.core.selection import compact_lane_map

        model = as_local_step(model)
        backend = self._resolve_backend(backend)
        fuse_sgd = backend == "pallas" and self._can_fuse_sgd(model, sampling)
        direct_iid = backend == "xla" and sampling == "iid"
        iid_core = self._iid_sgd_core(model, batch_size, max_iters) \
            if direct_iid else None
        local_train = None if (fuse_sgd or direct_iid) else \
            self._local_sgd(model, batch_size, max_iters, sampling)
        gather = self._cohort_gather(max_n, backend)

        def core(global_params, flat_x, flat_y, offsets, lengths, ids,
                 n_iters, rng, residual=None, corrupt=None):
            s = jax.lax.axis_index("data")
            C = offsets.shape[0]
            K = ids.shape[0]
            keys = jax.random.split(rng, K)
            if capacity is None:
                own = (ids // C) == s
                local = jnp.where(own, ids % C, 0)
                offs = offsets[local]
                n = jnp.where(own, jnp.minimum(lengths[local], max_n), 0)
                iters = jnp.where(own, n_iters, 0)
                executes = own
            else:
                # dense lane block: lane l serves cohort slot lane_map[l]
                # (sentinel K = unused lane) with that slot's own key,
                # budget and data — per-slot arithmetic is unchanged, only
                # the lane count shrinks from K to capacity
                lane_map = compact_lane_map(ids, C, s, capacity)
                lane_valid = lane_map < K
                slot = jnp.where(lane_valid, lane_map, 0)
                local = jnp.where(lane_valid, ids[slot] % C, 0)
                offs = offsets[local]
                n = jnp.where(lane_valid,
                              jnp.minimum(lengths[local], max_n), 0)
                iters = jnp.where(lane_valid, n_iters[slot], 0)
                keys = keys[slot]
                executes = lane_valid
            if fuse_sgd:
                with stage(STAGE_GATHER):
                    x, y, _ = gather(flat_x, flat_y, offs, n)
                with stage(STAGE_LOCAL_SGD):
                    params_k, losses = self._fused_sgd(
                        model, global_params, x, y, n, iters, keys,
                        batch_size, max_iters)
            elif direct_iid:
                def local_fn(off_k, nk, it, key):
                    return iid_core(global_params,
                                    lambda idx: (flat_x[off_k + idx],
                                                 flat_y[off_k + idx]),
                                    nk, it, key)

                with stage(STAGE_LOCAL_SGD):
                    params_k, losses = jax.vmap(local_fn)(offs, n, iters,
                                                          keys)
            else:
                with stage(STAGE_GATHER):
                    x, y, mask = gather(flat_x, flat_y, offs, n)
                with stage(STAGE_LOCAL_SGD):
                    params_k, losses = jax.vmap(
                        local_train, in_axes=(None, 0, 0, 0, 0, 0, 0))(
                        global_params, x, y, mask, n, iters, keys)

            if self.compressing:
                # stage 3: compress each executing lane's delta against the
                # residual row of the client it serves, then scatter the
                # updated rows back (C-sentinel drop for silent lanes;
                # writers never collide — cohort ids are distinct)
                uploaded_lane = executes & (iters > 0)
                resid_lane = uploaded_lane
                if corrupt is not None:
                    # per-lane view of the cohort corrupt mask (ISSUE 8):
                    # a sign_flip/explode lane transmits its corrupted
                    # delta (injected pre-transform, in-line — but a
                    # screened mode's residual write is dropped); nan/inf
                    # lanes are cut out of transmission, their garbage
                    # goes into the psum-rebuilt replicated stack in the
                    # caller
                    corrupt_lane = corrupt if capacity is None \
                        else corrupt[slot]
                    if self._inject_pre:
                        params_k = self._inject_faults(
                            global_params, params_k, corrupt_lane,
                            uploaded_lane)
                        if self._block_residual:
                            resid_lane = uploaded_lane & ~corrupt_lane
                    else:
                        uploaded_lane = uploaded_lane & ~corrupt_lane
                        resid_lane = uploaded_lane
                params_k, new_rows = self._upload_transform(
                    global_params, params_k, residual[local], uploaded_lane,
                    backend)
                rows = jnp.where(resid_lane, local, C)
                residual = residual.at[rows].set(new_rows, mode="drop")

            if capacity is None:
                def mask_slots(p):
                    shape = (-1,) + (1,) * (p.ndim - 1)
                    return jnp.where(own.reshape(shape), p,
                                     jnp.zeros((), p.dtype))

                params_k = jax.tree.map(
                    lambda p: jax.lax.psum(mask_slots(p), "data"), params_k)
                losses = jax.lax.psum(
                    jnp.where(own, losses, jnp.zeros((), losses.dtype)),
                    "data")
            else:
                def scatter_slots(p):
                    # lane results back to global [K] rows; sentinel lanes
                    # and overflowed slots stay exact zeros, so the psum is
                    # still the ownership-masked rebuild
                    z = jnp.zeros((K,) + p.shape[1:], p.dtype)
                    return z.at[lane_map].set(p, mode="drop")

                params_k = jax.tree.map(
                    lambda p: jax.lax.psum(scatter_slots(p), "data"),
                    params_k)
                losses = jax.lax.psum(scatter_slots(losses), "data")
            if self.compressing:
                return params_k, losses, residual
            return params_k, losses

        return core

    def _sharded_round_fn(self, model, batch_size: int, max_iters: int,
                          max_n: int, sampling: str, backend: Optional[str],
                          mesh, capacity: Optional[int] = None) -> Callable:
        """Un-jitted sharded packed round: ``shard_map`` around
        :meth:`_shard_round_core`, aggregation on the psum-rebuilt stack.

        With ``capacity`` set, the budgets of overflowed cohort slots
        (``cohort_overflow`` — owned-slot rank >= capacity) are zeroed
        BEFORE the shard_map and the aggregation weights, so an overflowed
        slot can never contribute a nonzero weight to a zero stack row even
        if the caller forgot to drop it server-side."""
        from jax.sharding import PartitionSpec as P

        from repro.core.selection import cohort_overflow

        core = self._shard_round_core(model, batch_size, max_iters, max_n,
                                      sampling, backend, capacity)
        compressing = self.compressing
        injecting, screening = self.injecting, self.screening

        def round_fn(global_params, flat_x, flat_y, offsets, lengths, ids,
                     n_iters, rng, *extra):
            # trailing args mirror the server's positional convention:
            # residual (compressing only), then corrupt (injecting only)
            residual = extra[0] if compressing else None
            corrupt = extra[-1] if injecting else None
            _check_shard_count(flat_x, mesh)
            if capacity is not None:
                n_iters = jnp.where(
                    cohort_overflow(ids, lengths.shape[1], capacity),
                    0, n_iters)

            if compressing and injecting:
                # residual shards with the client axis; the cohort corrupt
                # mask is replicated like ids/budgets
                def shard_fn(gp, x, y, offs, lens, ids_, it_, rng_, res,
                             cor):
                    pk, ls, res = core(gp, x[0], y[0], offs[0], lens[0],
                                       ids_, it_, rng_, res[0], cor)
                    return pk, ls, res[None]

                params_k, losses, residual = jax.shard_map(
                    shard_fn, mesh=mesh, check_vma=False,
                    in_specs=(P(), P("data"), P("data"), P("data"),
                              P("data"), P(), P(), P(), P("data"), P()),
                    out_specs=(P(), P(), P("data")))(
                    global_params, flat_x, flat_y, offsets, lengths, ids,
                    n_iters, rng, residual, corrupt)
            elif compressing:
                # residual [S, C, P] shards with the client axis: each
                # shard updates only its own clients' rows
                def shard_fn(gp, x, y, offs, lens, ids_, it_, rng_, res):
                    pk, ls, res = core(gp, x[0], y[0], offs[0], lens[0],
                                       ids_, it_, rng_, res[0])
                    return pk, ls, res[None]

                params_k, losses, residual = jax.shard_map(
                    shard_fn, mesh=mesh, check_vma=False,
                    in_specs=(P(), P("data"), P("data"), P("data"),
                              P("data"), P(), P(), P(), P("data")),
                    out_specs=(P(), P(), P("data")))(
                    global_params, flat_x, flat_y, offsets, lengths, ids,
                    n_iters, rng, residual)
            else:
                def shard_fn(gp, x, y, offs, lens, ids_, it_, rng_):
                    return core(gp, x[0], y[0], offs[0], lens[0], ids_, it_,
                                rng_)

                params_k, losses = jax.shard_map(
                    shard_fn, mesh=mesh, check_vma=False,
                    in_specs=(P(), P("data"), P("data"), P("data"),
                              P("data"), P(), P(), P()),
                    out_specs=(P(), P()))(
                    global_params, flat_x, flat_y, offsets, lengths, ids,
                    n_iters, rng)
            if self._inject_post:
                # corrupt the psum-rebuilt replicated stack "on the wire"
                # (nan/inf garbage is value-independent, so it needs no
                # lane ownership — the mask is replicated)
                params_k = self._inject_faults(global_params, params_k,
                                               corrupt, n_iters > 0)
            # [S, C] lengths flatten to global-id order (shard s owns the
            # contiguous block [s*C, (s+1)*C)), so the aggregation weights
            # match the replicated round exactly
            n = jnp.minimum(lengths.reshape(-1)[ids], max_n)
            new_global, any_up, bad = self._finish(
                global_params, params_k, self._upload_weights(n, n_iters))
            out = (new_global, losses, any_up)
            if compressing:
                out = out + (residual,)
            if screening:
                out = out + (bad,)
            return out

        return round_fn

    # ------------------------------------------------------------------
    # fused multi-round segment: whole training blocks in one lax.scan
    # ------------------------------------------------------------------
    def make_segment_fn(self, model, batch_size: int, max_iters: int,
                        max_n: int, cfg, sampling: Optional[str] = None,
                        backend: Optional[str] = None,
                        mesh=None, telemetry: bool = False) -> Callable:
        """Fuse whole FedSAE training segments into one jitted ``lax.scan``.

        segment_fn(state, ts, flat_x, flat_y, offsets, lengths, mu, sigma)
            -> (state', stats)

        With ``compress="topk_q8"`` (engine option) the segment takes a
        trailing error-feedback ``residual`` argument ([N, P] replicated,
        [S, C, P] sharded) and returns ``(state', residual', stats)`` —
        the residual joins the ``lax.scan`` carry inside the segment, so
        compressed multi-round blocks still dispatch once.

        ``state`` is the scan carry — a dict with keys

            params    model pytree
            L, H      [N] float32 task-pair history
            theta     [N] float32 Fassa EMA thresholds
            values    [N] float32 AL training values
            data_rng  threefry key for minibatch draws
            sel_rng   threefry key for selection + heterogeneity draws

        and ``ts`` the [block] int32 round indices to execute.  Each scanned
        round runs the FULL server step on device: heterogeneity draw
        (``sample_workloads_device``), cohort selection (Gumbel-top-k,
        ``select_cohort_device``), workload prediction + history update
        (``workload_update_device`` — Ira/Fassa/fixed-workload baselines),
        budgeted local SGD + aggregation, and the ValueTracker scatter.
        Zero bytes cross the host boundary inside a block; the caller pulls
        ``stats`` (per-round [block] arrays: dropout, train_loss, assigned,
        uploaded, true_workload, and the [block, K] cohort ``ids``) once per
        segment.

        ``cfg`` is duck-typed ``ServerConfig`` (algo / n_selected /
        al_rounds / beta / selection / U / alpha / gamma1 / gamma2 / h_cap /
        fixed_epochs).  ``sampling``/``backend`` default to ``cfg``'s
        values; ``backend="pallas"`` composes the fed_gather/fed_local_sgd
        kernels under the scan unchanged.  With the default XLA backend and
        ``sampling="iid"`` the round body indexes minibatches straight out
        of the packed arrays (``_direct_iid_round_body``) — no [K, max_n,
        feat] cohort shard is ever materialized.

        All float state is pinned float32 (also under ``jax_enable_x64``);
        the carried history never leaves device, so a block is one XLA
        program and one dispatch.

        ``mesh`` (ISSUE 4): a 1-D ``data`` mesh shards the whole segment —
        packed arrays arrive in the [S, ...] sharded layout, the cohort is
        selected by a local-top-k -> all-gather -> global-merge (bitwise
        the replicated Gumbel-top-k), each shard trains only the cohort
        slots it owns (:meth:`_shard_round_core`), and the history /
        ValueTracker math runs replicated on every shard.  One ``shard_map``
        wraps the whole block, so the scan still dispatches once per
        segment.

        ``cfg.cohort_capacity`` (ISSUE 5, sharded only): "full" keeps the
        masked full-K round; "auto" or an int compacts each shard to a
        dense capacity-sized lane block inside the scanned round body,
        with overflowed slots dropped through the Ira/Fassa crash branch
        and counted in the per-round ``overflowed`` stat (the resolution
        lives in ``repro.core.selection.resolve_capacity``).

        ``cfg.prefetch`` (ISSUE 10): "off" (default) runs the classic one
        scanned round per step; "double_buffer" splits every round into
        prepare/execute halves and carries the prepared bundle across
        scan steps (``_scan_prefetch``), so cohort t+1's selection +
        budget math + data gather is issued in the same program region
        as cohort t's local SGD.  Bit-identical results in both modes
        (replicated driver only; a sharded mesh raises).

        ``telemetry`` (ISSUE 7): device-computed metric accumulation.  The
        per-round stats gain ``client_uploaded`` ([K] per-slot upload
        outcome), ``upload_bytes``/``dense_upload_bytes`` (the
        compressed-vs-dense byte ledger under the configured upload
        transform) and fixed-bin ``loss_hist``/``workload_hist``
        (geometry in ``repro.obs.schema``; numpy twin
        ``histogram_counts``).  Everything rides the block's single
        existing stats pull — host_syncs_per_round does NOT change — and
        all extras are derived from replicated values, so the sharded
        segment needs no extra collectives.  ``telemetry=False``
        (default) emits the exact PR-6 stats dict: the traced program is
        unchanged, keeping untelemetered runs bitwise identical
        (tests/test_telemetry.py).
        """
        from repro.core import prediction as pred
        from repro.core.heterogeneity import sample_workloads_device
        from repro.core.selection import (resolve_capacity,
                                          select_cohort_device,
                                          value_update_device)
        from repro.faults import (apply_availability_stragglers,
                                  corrupt_mask, dropout_mask, eligibility,
                                  quarantine_update)

        sampling = cfg.sampling if sampling is None else sampling
        backend = self._resolve_backend(
            getattr(cfg, "backend", None) if backend is None else backend)

        algo = cfg.algo
        K = int(cfg.n_selected)
        capacity = resolve_capacity(
            getattr(cfg, "cohort_capacity", "full"), K,
            mesh.shape["data"] if mesh is not None else 0)
        al_rounds = int(getattr(cfg, "al_rounds", 0))
        beta = float(getattr(cfg, "beta", 0.01))
        strategy = getattr(cfg, "selection", "random")
        wl_kwargs = dict(
            U=float(cfg.U), alpha=float(cfg.alpha),
            gamma1=float(cfg.gamma1), gamma2=float(cfg.gamma2),
            h_cap=float(cfg.h_cap), fixed_epochs=float(cfg.fixed_epochs))
        telemetry = bool(telemetry)

        # ISSUE 10: double-buffered cohort prefetch.  "off" traces the
        # exact pre-prefetch program (the round is still composed as
        # execute(prepare(...)) in one scan step); "double_buffer" carries
        # next round's prepared bundle — selection, budgets, the gathered
        # cohort data — across scan steps so cohort t+1's gather sits in
        # the same XLA program region as cohort t's local SGD.
        prefetch = getattr(cfg, "prefetch", "off") or "off"
        if prefetch not in PREFETCH_MODES:
            raise ValueError(
                f"unknown prefetch mode {prefetch!r}; choose from "
                f"{PREFETCH_MODES}")
        if prefetch != "off" and mesh is not None:
            raise ValueError(
                "prefetch=\"double_buffer\" is not supported on a sharded "
                "mesh yet (the prepared bundle would need per-shard "
                "carries through shard_map; run prefetch on the "
                "replicated scan driver)")

        # ISSUE 8: fault + defense wiring.  With faults=None and screening
        # off every branch below is statically absent, so the traced
        # program is bitwise the PR-7 one.
        fm = self.faults
        injecting, screening = self.injecting, self.screening
        q_threshold = float(
            getattr(cfg, "quarantine_threshold", 0.0) or 0.0)
        quarantine = q_threshold > 0.0
        q_rounds = int(getattr(cfg, "quarantine_rounds", 16))
        q_min_tries = int(getattr(cfg, "quarantine_min_tries", 3))
        if quarantine and mesh is not None:
            raise ValueError(
                "quarantine_threshold > 0 is not supported on a sharded "
                "mesh (per-client reliability counters would need an "
                "extra replicated carry audit; run quarantine on the "
                "replicated scan driver)")
        if quarantine and not screening:
            raise ValueError(
                "quarantine_threshold > 0 requires the upload screen "
                "(screen_norm) — quarantine counts screened failures")

        def make_one_round(select, train, sizes, mu, sigma, overflow=None,
                           prep_data=None):
            """The per-round server step, shared verbatim by the replicated
            and the sharded segment — only cohort selection, the training
            dispatch, the client-size lookup and the capacity-overflow mask
            differ between them.

            ``overflow(ids) -> [K] bool`` marks cohort slots dropped by the
            capacity policy (None = nothing overflows).  An overflowed
            client's E~ is forced to 0 BEFORE the workload update, so its
            Ira/Fassa history takes the existing crash branch (outcome
            DROPPED, L/H halved, zero uploaded epochs -> zero budget) and
            the self-adaptive estimator absorbs the drop exactly like a
            paper-style straggler; the drawn E~ still feeds the
            ``true_workload`` stat.

            Under compression the carry additionally holds the
            error-feedback ``residual`` and ``train`` threads it:
            train(params, residual, ids, n_iters, sub) -> (params,
            residual, losses).

            Fault semantics (ISSUE 8): availability/straggler faults
            rescale E~ BEFORE selection sees anything (a slowed client is
            just a weaker client to Ira/Fassa).  Seeded dropout zeroes
            E_run like an overflow.  Screened corruption modes
            (crash/nan/inf/explode) zero the OBSERVED workload so the
            history update takes the crash branch — the Ira/Fassa state
            evolves bitwise like the crash-twin run — while injected modes
            still train with the un-demoted budget (the garbage the client
            would actually transmit) and the upload screen in ``_finish``
            restores the crash-row (weight 0, global-row) outcome.
            ``sign_flip`` is NOT demoted: the server cannot tell a flipped
            delta from a real one, so it uploads normally and robust
            aggregation is the defense.

            The round is built as ``execute(prepare(carry, t))`` and the
            two halves are exported as ``one_round.prepare`` /
            ``one_round.execute`` (ISSUE 10): ``prepare`` runs everything
            upstream of training — heterogeneity draw, selection, the
            Ira/Fassa history update, budgets, the round's data_rng split
            and (with a ``prep_data`` hook) the cohort data gather — into
            a prefetch bundle ``pf``; ``execute`` consumes the bundle
            (training, value update, stats, quarantine).  The default
            ``one_round`` composes them back-to-back, emitting ops in
            exactly the pre-split order, so the off-mode traced program is
            unchanged; the double-buffered segment driver instead carries
            ``pf`` across scan steps (``_scan_prefetch``).

            ``prep_data(ids, sub) -> data`` pre-gathers the cohort's
            training data into the bundle; ``train`` then receives it as a
            trailing ``data=`` keyword."""
            compressing = self.compressing
            phases = None if fm is None else fm.phases(int(mu.shape[0]))
            if phases is not None:
                phases = jnp.asarray(phases)
            n_clients = int(mu.shape[0])
            demote = fm is not None and fm.demotes

            def prepare(carry, t):
                L, H, theta = carry["L"], carry["H"], carry["theta"]
                values = carry["values"]
                sel_rng, k_sel, k_het = jax.random.split(carry["sel_rng"], 3)
                E_all = sample_workloads_device(k_het, mu, sigma)
                if fm is not None:
                    E_all = apply_availability_stragglers(fm, phases, t,
                                                          E_all)
                if quarantine:
                    ids = select(k_sel, values, t,
                                 eligibility(carry["q_susp"], t))
                else:
                    ids = select(k_sel, values, t)
                E_true = E_all[ids]
                ovf = (jnp.zeros(ids.shape, bool) if overflow is None
                       else overflow(ids))
                E_run = jnp.where(ovf, jnp.float32(0.0), E_true)
                if fm is not None and fm.dropout_prob > 0.0:
                    drop = dropout_mask(fm, t, n_clients)[ids]
                    E_run = jnp.where(drop, jnp.float32(0.0), E_run)
                corrupt = (corrupt_mask(fm, t, n_clients)[ids]
                           if fm is not None and fm.corrupts else None)
                E_obs = (jnp.where(corrupt, jnp.float32(0.0), E_run)
                         if demote else E_run)
                e_eff, outcome, assigned, L_new, H_new, theta_new = \
                    pred.workload_update_device(algo, L, H, theta, ids,
                                                E_obs, **wl_kwargs)
                if demote and injecting:
                    # the faulty client doesn't know it will be screened:
                    # it trains with the UN-demoted budget (same old
                    # history, real E~) and transmits garbage.  ids are
                    # unique, so per-row e_eff matches the observed call
                    # bitwise on every non-corrupt row.
                    e_train = pred.workload_update_device(
                        algo, L, H, theta, ids, E_run, **wl_kwargs)[0]
                else:
                    e_train = e_eff
                n = jnp.minimum(sizes[ids], max_n)
                n_iters = budget_iters(e_train, n, batch_size, max_iters)
                data_rng, sub = jax.random.split(carry["data_rng"])
                new_carry = dict(carry, L=L_new, H=H_new, theta=theta_new,
                                 sel_rng=sel_rng, data_rng=data_rng)
                pf = {"t": t, "ids": ids, "n_iters": n_iters, "sub": sub,
                      "ovf": ovf, "outcome": outcome, "assigned": assigned,
                      "e_eff": e_eff, "E_true": E_true}
                if injecting:
                    pf["corrupt"] = corrupt
                if prep_data is not None:
                    pf["data"] = prep_data(ids, sub)
                return new_carry, pf

            def execute(carry, pf):
                params = carry["params"]
                values = carry["values"]
                L, H, theta = carry["L"], carry["H"], carry["theta"]
                t, ids = pf["t"], pf["ids"]
                n_iters, sub = pf["n_iters"], pf["sub"]
                ovf, outcome = pf["ovf"], pf["outcome"]
                assigned, e_eff, E_true = (pf["assigned"], pf["e_eff"],
                                           pf["E_true"])
                corrupt = pf.get("corrupt")
                if compressing:
                    targs = (params, carry["residual"], ids, n_iters, sub)
                else:
                    targs = (params, ids, n_iters, sub)
                if injecting:
                    targs = targs + (corrupt,)
                tkw = {} if prep_data is None else {"data": pf["data"]}
                out = train(*targs, **tkw)
                if compressing:
                    params, residual, losses = out[0], out[1], out[2]
                else:
                    params, losses = out[0], out[1]
                bad = out[-1] if screening else None
                uploaded = n_iters > 0
                if demote and injecting:
                    # the observed upload set: screened-out rows count as
                    # crashes, bitwise the crash-twin's (n_iters > 0)
                    uploaded = uploaded & ~corrupt
                values = value_update_device(values, sizes, ids, losses,
                                             uploaded)
                upf = uploaded.astype(jnp.float32)
                n_up = upf.sum()
                stats = {
                    "ids": ids,
                    "dropout": (outcome == pred.DROPPED)
                        .astype(jnp.float32).mean(),
                    "dropped": (outcome == pred.DROPPED)
                        .astype(jnp.float32).sum(),
                    "overflowed": ovf.astype(jnp.float32).sum(),
                    "train_loss": jnp.where(
                        n_up > 0,
                        (losses * upf).sum() / jnp.maximum(n_up, 1.0),
                        jnp.float32(jnp.nan)),
                    "assigned": assigned.mean(),
                    "uploaded": e_eff.mean(),
                    "true_workload": E_true.mean(),
                }
                if telemetry:
                    # ISSUE 7: device-accumulated extras that ride the
                    # block's single stats pull.  All derived from
                    # replicated values, so the sharded segment carries
                    # them with no extra collectives; with telemetry off
                    # this branch vanishes and the program is bitwise
                    # the untelemetered one.
                    from repro.core.compression import (
                        n_params_of, upload_bytes_per_client)
                    from repro.obs.schema import (LOSS_HIST_BINS,
                                                  LOSS_HIST_MAX,
                                                  WORKLOAD_HIST_BINS)
                    P = n_params_of(params)
                    bpc = upload_bytes_per_client(P, self.compress,
                                                  self.topk_frac)
                    dense_bpc = upload_bytes_per_client(P, "none")
                    stats["client_uploaded"] = uploaded
                    stats["upload_bytes"] = n_up * jnp.float32(bpc)
                    stats["dense_upload_bytes"] = n_up \
                        * jnp.float32(dense_bpc)
                    stats["loss_hist"] = _device_hist(
                        losses, upf, 0.0, LOSS_HIST_MAX, LOSS_HIST_BINS)
                    stats["workload_hist"] = _device_hist(
                        e_eff, upf, 0.0, wl_kwargs["h_cap"],
                        WORKLOAD_HIST_BINS)
                new_carry = {"params": params, "L": L, "H": H,
                             "theta": theta, "values": values,
                             "data_rng": carry["data_rng"],
                             "sel_rng": carry["sel_rng"]}
                if screening:
                    stats["screened"] = bad.sum().astype(jnp.float32)
                if quarantine:
                    q_fail, q_try, q_susp, n_susp = quarantine_update(
                        carry["q_fail"], carry["q_try"], carry["q_susp"],
                        ids, n_iters > 0, bad, t, q_threshold, q_rounds,
                        q_min_tries)
                    new_carry["q_fail"] = q_fail
                    new_carry["q_try"] = q_try
                    new_carry["q_susp"] = q_susp
                    stats["quarantined"] = n_susp.astype(jnp.float32)
                if compressing:
                    new_carry["residual"] = residual
                return new_carry, stats

            def one_round(carry, t):
                carry, pf = prepare(carry, t)
                return execute(carry, pf)

            one_round.prepare = prepare
            one_round.execute = execute
            return one_round

        if mesh is not None:
            return self._jit_round(self._sharded_segment(
                model, batch_size, max_iters, max_n, sampling, backend,
                mesh, K, strategy, beta, al_rounds, make_one_round,
                capacity),
                donate=(0, 8) if self.compressing else (0,))

        if backend == "xla" and sampling == "iid":
            # the segment honors cfg's fused_generic over the engine's
            # constructor default, so direct make_segment_fn callers (the
            # bench's unfused-baseline leg) get the walk the cfg names
            round_body = self._direct_iid_round_body(
                model, batch_size, max_iters, max_n,
                fused=getattr(cfg, "fused_generic", None))
        else:
            round_body = self._packed_round_body(
                model, batch_size, max_iters, max_n, sampling, backend)

        prefetching = prefetch == "double_buffer"
        if prefetching:
            prep_flat, train_data = self._prefetched_round_parts(
                model, batch_size, max_iters, max_n, sampling, backend)

        if self.compressing:
            def segment(state, ts, flat_x, flat_y, offsets, lengths, mu,
                        sigma, residual):
                def select(k_sel, values, t, elig=None):
                    return select_cohort_device(k_sel, values, K, strategy,
                                                beta, use_al=t < al_rounds,
                                                elig=elig)

                if prefetching:
                    def prep_data(ids, sub):
                        return prep_flat(flat_x, flat_y, offsets, lengths,
                                         ids, sub)

                    def train(params, residual, ids, n_iters, sub,
                              corrupt=None, data=None):
                        params_k, losses, n = train_data(params, data,
                                                         n_iters, sub)
                        out = self._finish_round(
                            params, params_k, losses, n, n_iters, backend,
                            residual=residual, ids=ids, corrupt=corrupt)
                        if screening:
                            return out[0], out[3], out[1], out[4]
                        return out[0], out[3], out[1]

                    one_round = make_one_round(select, train, lengths, mu,
                                               sigma, prep_data=prep_data)
                    carry = dict(state)
                    carry["residual"] = residual
                    carry, stats = _scan_prefetch(one_round, carry, ts)
                    residual = carry.pop("residual")
                    return carry, residual, stats

                def train(params, residual, ids, n_iters, sub,
                          corrupt=None):
                    args = (params, flat_x, flat_y, offsets, lengths, ids,
                            n_iters, sub, residual)
                    if corrupt is not None:
                        args = args + (corrupt,)
                    out = round_body(*args)
                    if screening:
                        return out[0], out[3], out[1], out[4]
                    return out[0], out[3], out[1]

                one_round = make_one_round(select, train, lengths, mu,
                                           sigma)
                carry = dict(state)
                carry["residual"] = residual
                carry, stats = jax.lax.scan(one_round, carry, ts)
                residual = carry.pop("residual")
                return carry, residual, stats
        else:
            def segment(state, ts, flat_x, flat_y, offsets, lengths, mu,
                        sigma):
                def select(k_sel, values, t, elig=None):
                    return select_cohort_device(k_sel, values, K, strategy,
                                                beta, use_al=t < al_rounds,
                                                elig=elig)

                if prefetching:
                    def prep_data(ids, sub):
                        return prep_flat(flat_x, flat_y, offsets, lengths,
                                         ids, sub)

                    def train(params, ids, n_iters, sub, corrupt=None,
                              data=None):
                        params_k, losses, n = train_data(params, data,
                                                         n_iters, sub)
                        out = self._finish_round(
                            params, params_k, losses, n, n_iters, backend,
                            corrupt=corrupt)
                        if screening:
                            return out[0], out[1], out[3]
                        return out[0], out[1]

                    one_round = make_one_round(select, train, lengths, mu,
                                               sigma, prep_data=prep_data)
                    return _scan_prefetch(one_round, state, ts)

                def train(params, ids, n_iters, sub, corrupt=None):
                    args = (params, flat_x, flat_y, offsets, lengths, ids,
                            n_iters, sub)
                    if corrupt is not None:
                        args = args + (corrupt,)
                    out = round_body(*args)
                    if screening:
                        return out[0], out[1], out[3]
                    return out[0], out[1]

                one_round = make_one_round(select, train, lengths, mu,
                                           sigma)
                return jax.lax.scan(one_round, state, ts)

        # the caller reassigns state (argnum 0) and, when compressing, the
        # error-feedback residual (argnum 8) from the outputs every block,
        # so both buffers are donation-dead on entry (ISSUE 10 audit:
        # tests/test_fused_generic.py)
        return self._jit_round(
            segment, donate=(0, 8) if self.compressing else (0,))

    def _sharded_segment(self, model, batch_size: int, max_iters: int,
                         max_n: int, sampling: str, backend: str, mesh,
                         K: int, strategy: str, beta: float, al_rounds: int,
                         make_one_round,
                         capacity: Optional[int] = None) -> Callable:
        """Un-jitted sharded multi-round segment: one ``shard_map`` around
        the whole ``lax.scan`` block (see :meth:`make_segment_fn`).

        ``capacity`` selects compacted execution inside the scanned round
        body (:meth:`_shard_round_core`); the overflow mask is computed per
        round from the freshly selected cohort and applied both to the
        Ira/Fassa update (crash branch, via ``make_one_round``'s overflow
        hook) and, defensively, to the budgets entering the round."""
        from jax.sharding import PartitionSpec as P

        from repro.core.selection import (_cohort_scores, cohort_overflow,
                                          local_topk_candidates,
                                          merge_topk_candidates, pad_scores)

        core = self._shard_round_core(model, batch_size, max_iters, max_n,
                                      sampling, backend, capacity)
        n_shards = mesh.shape["data"]
        compressing = self.compressing

        def segment(state, ts, flat_x, flat_y, offsets, lengths, mu, sigma,
                    residual=None):
            _check_shard_count(flat_x, mesh)

            def shard_seg(state, ts, x, y, offs, lens, mu, sigma,
                          res=None):
                x, y, offs, lens = x[0], y[0], offs[0], lens[0]
                s = jax.lax.axis_index("data")
                C = offs.shape[0]
                # global client sizes in id order — replicated, tiny
                sizes = jax.lax.all_gather(lens, "data").reshape(-1)

                def select(k_sel, values, t):
                    scores = _cohort_scores(k_sel, values, strategy, beta,
                                            use_al=t < al_rounds)
                    scores_pad, _ = pad_scores(scores, n_shards)
                    vals, gids = local_topk_candidates(scores_pad, s, C, K)
                    cand_v = jax.lax.all_gather(vals, "data")
                    cand_i = jax.lax.all_gather(gids, "data")
                    return merge_topk_candidates(cand_v, cand_i,
                                                 n_shards * C, K)

                overflow = None if capacity is None else \
                    (lambda ids_: cohort_overflow(ids_, C, capacity))

                if compressing:
                    def train(params, residual, ids, n_iters, sub,
                              corrupt=None):
                        if capacity is not None:
                            n_iters = jnp.where(cohort_overflow(ids, C,
                                                                capacity),
                                                0, n_iters)
                        cargs = (params, x, y, offs, lens, ids, n_iters,
                                 sub, residual)
                        if corrupt is not None:
                            cargs = cargs + (corrupt,)
                        params_k, losses, residual = core(*cargs)
                        if self._inject_post and corrupt is not None:
                            params_k = self._inject_faults(
                                params, params_k, corrupt, n_iters > 0)
                        n = jnp.minimum(sizes[ids], max_n)
                        new_global, _, bad = self._finish(
                            params, params_k,
                            self._upload_weights(n, n_iters))
                        if self.screening:
                            return new_global, residual, losses, bad
                        return new_global, residual, losses
                else:
                    def train(params, ids, n_iters, sub, corrupt=None):
                        if capacity is not None:
                            n_iters = jnp.where(cohort_overflow(ids, C,
                                                                capacity),
                                                0, n_iters)
                        params_k, losses = core(params, x, y, offs, lens,
                                                ids, n_iters, sub)
                        if self._inject_post and corrupt is not None:
                            params_k = self._inject_faults(
                                params, params_k, corrupt, n_iters > 0)
                        n = jnp.minimum(sizes[ids], max_n)
                        new_global, _, bad = self._finish(
                            params, params_k,
                            self._upload_weights(n, n_iters))
                        if self.screening:
                            return new_global, losses, bad
                        return new_global, losses

                one_round = make_one_round(select, train, sizes, mu, sigma,
                                           overflow)
                if compressing:
                    # shard-local residual rows join the scan carry
                    carry = dict(state)
                    carry["residual"] = res[0]
                    carry, stats = jax.lax.scan(one_round, carry, ts)
                    res_out = carry.pop("residual")
                    return carry, res_out[None], stats
                return jax.lax.scan(one_round, state, ts)

            if compressing:
                state, residual, stats = jax.shard_map(
                    shard_seg, mesh=mesh, check_vma=False,
                    in_specs=(P(), P(), P("data"), P("data"), P("data"),
                              P("data"), P(), P(), P("data")),
                    out_specs=(P(), P("data"), P()))(
                    state, ts, flat_x, flat_y, offsets, lengths, mu, sigma,
                    residual)
                return state, residual, stats
            return jax.shard_map(
                shard_seg, mesh=mesh, check_vma=False,
                in_specs=(P(), P(), P("data"), P("data"), P("data"),
                          P("data"), P(), P()),
                out_specs=(P(), P()))(
                state, ts, flat_x, flat_y, offsets, lengths, mu, sigma)

        return segment

    # ------------------------------------------------------------------
    def make_stream_round(self, loss_fn, max_steps: int,
                          backend: Optional[str] = None) -> Callable:
        """Cross-silo round over pre-batched per-silo streams.

        ``loss_fn`` is either a bare ``loss(params, batch)`` callable (the
        pre-LocalStep silo interface) or any ``LocalStep``-coercible model
        — both land on the same scanned local-SGD body, and aggregation
        runs through the shared :meth:`_finish` stage, so the silo path
        rides the same screen/aggregator seam as the packed rounds.

        round_fn(global_params, batches, n_steps, weights) ->
            (new_global_params, silo_mean_losses[, bad])
          batches: pytree with leading axes [K, max_steps, ...]
          n_steps: [K] int32 masked local-step budgets
          weights: [K] f32 aggregation weights (0 = no upload)
          bad:     [K] bool screen verdicts (only with ``screen_norm``)

        ``backend`` is accepted for interface uniformity; no fused kernel
        applies to arbitrary batch pytrees, so "pallas" falls back to the
        XLA scan (the flag is validated either way).
        """
        if self.compressing:
            raise ValueError(
                "upload compression needs the packed client axis for "
                "residual state; the cross-silo stream round does not "
                "support it")
        if self.injecting:
            raise ValueError(
                "fault injection targets the packed client-axis rounds; "
                "the cross-silo stream round does not support it")
        if not callable(loss_fn):
            loss_fn = as_local_step(loss_fn).loss
        self._resolve_backend(backend)
        lr = self.lr
        screening = self.screening

        def local_train(global_params, silo_batches, n_steps):
            def step(params, xs):
                i, batch = xs

                def obj(p):
                    return self._prox(loss_fn(p, batch), p, global_params)

                loss, g = jax.value_and_grad(obj)(params)
                active = (i < n_steps).astype(jnp.float32)
                params = jax.tree.map(lambda p, gg: p - lr * active
                                      * gg.astype(p.dtype), params, g)
                return params, loss

            params, losses = jax.lax.scan(
                step, global_params, (jnp.arange(max_steps), silo_batches))
            # mean loss over executed steps only
            msk = (jnp.arange(max_steps) < n_steps).astype(jnp.float32)
            mean_loss = (losses * msk).sum() / jnp.maximum(msk.sum(), 1)
            return params, mean_loss

        def round_fn(global_params, batches, n_steps, weights):
            params_k, losses = jax.vmap(local_train, in_axes=(None, 0, 0))(
                global_params, batches, n_steps)
            new_global, _, bad = self._finish(global_params, params_k,
                                              weights)
            if screening:
                return new_global, losses, bad
            return new_global, losses

        return self._jit_round(round_fn)
