#!/usr/bin/env python3
"""Chip smoke test: FedSAE's paper-scale scan driver on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the client-sharded path on four chips

One chip runs the deployment of ``examples/paper_scale_fl.py`` through the
public ``FedSAEServer.run``: the 1,000-client MNIST-like federation
(``make_mnist_like()`` defaults, d=784, 10 classes), MCLR (7,850 params),
FedSAE-Fassa with K=30 clients a round, h_cap=24 and batch 10 (960
local-SGD slots a client), iid minibatches, the scan driver in two blocks
of 16 rounds.  One line per phase:

  device      platform, device kind and count; anything but a TPU exits 1
  xla         backend="xla": compile seconds, steady seconds per block,
              accuracy, mean dropout, whether every param is finite
  pallas      backend="pallas": the compiled segment holds the Mosaic
              kernels (tpu_custom_call); final params agree with the xla run
  compressed  backend="pallas" with upload_compress="topk_q8", so the
              fed_compress kernel runs too; the kernel against its
              reference twin on the same rows
  reference   one round of each backend on the chip against the plain
              padded round (RoundEngine.make_padded_round) on this
              process's CPU device: same cohort, budgets and key

``--chips 4`` runs only the sharded path (mesh_shards=4,
cohort_capacity="auto") and the replicated run it is compared with.

Every phase raises on failure, so the script then exits non-zero without
the result line.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process: a child would find the chip held.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The default f32 matmul on a TPU, in XLA and in Mosaic, rounds its
# operands to bf16 (unit roundoff 2^-9); the CPU reference keeps f32.  One
# round from identical params can move the flattened params by at most
# what rounding each of them once to bf16 does (relative L2 2^-9).  Over
# two blocks the drift has no such bound; 5x leaves a wide margin over the
# 2.1e-4 that xla at default vs "highest" precision drifts on a TPU v5e
# (the same as a CPU emulation of bf16 operands).  A wrong window, label
# or gradient moves the params by O(1).
ROUND_RTOL = 2.0 ** -9
RUN_RTOL = 5 * ROUND_RTOL
MIN_ACC = 0.5          # 10 classes: chance is 0.1
ROUNDS, BLOCK = 32, 16

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def require(cond, what: str):
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling, the programs it
    compiles, and its persistent-cache hits (``jax.monitoring`` events)."""

    def __init__(self, jax):
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in (_TRACE, _LOWER, _COMPILE):
            self.seconds += secs
        if event == _COMPILE:
            self.programs += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1

    def mark(self):
        return self.seconds, self.programs, self.hits

    def since(self, mark):
        return (self.seconds - mark[0], self.programs - mark[1],
                self.hits - mark[2])


def flat(params):
    import jax
    import numpy as np
    return np.concatenate([np.ravel(np.asarray(leaf, np.float64))
                           for leaf in jax.tree.leaves(params)])


def deviation(a, b):
    """(relative L2, max abs) deviation of params ``a`` from ``b``."""
    import numpy as np
    fa, fb = flat(a), flat(b)
    return (float(np.linalg.norm(fa - fb) / np.linalg.norm(fb)),
            float(np.abs(fa - fb).max()))


def paper_config(backend: str, seed: int, **kw):
    from repro.core import CommConfig, ComputeConfig, ServerConfig
    compress = kw.pop("upload_compress", "none")
    return ServerConfig(
        algo="fassa", n_selected=30, lr=0.03, batch_size=10, rounds=ROUNDS,
        h_cap=24.0, eval_every=BLOCK, sampling="iid", seed=seed,
        compute=ComputeConfig(driver="scan", block_size=BLOCK,
                              backend=backend, **kw),
        comm=CommConfig(upload_compress=compress))


def segment_kernels(srv) -> int:
    """tpu_custom_call sites in the server's compiled scan segment."""
    import jax
    import jax.numpy as jnp
    pk = srv.packed
    args = (srv.device_state(), jnp.arange(BLOCK, dtype=jnp.int32), pk.x,
            pk.y, pk.offsets, pk.lengths, *srv.het.device_params())
    if srv.residual is not None:
        args += (srv.residual,)
    fn = srv.segment_fn
    text = jax.jit(fn._fn, donate_argnums=fn.donate_argnums).lower(
        *args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def train(label, ds, cfg, meter):
    """Run the config's two blocks, then time one more (already compiled)
    block; returns (server, final params on the host, history)."""
    import jax
    import numpy as np

    from repro.core import FedSAEServer, HeterogeneitySim
    from repro.kernels.ops import fused_sgd_eligible
    from repro.models.fl_models import make_mclr

    model = make_mclr(ds.clients_x[0].shape[1], ds.n_classes)
    srv = FedSAEServer(ds, model, cfg,
                       het=HeterogeneitySim(ds.n_clients, seed=cfg.seed))
    mark = meter.mark()
    t0 = time.perf_counter()
    hist = srv.run()
    jax.block_until_ready(srv.params)
    first_s = time.perf_counter() - t0
    compile_s, programs, hits = meter.since(mark)
    params = jax.device_get(srv.params)

    mark = meter.mark()
    t0 = time.perf_counter()
    srv.run(rounds=BLOCK)
    jax.block_until_ready(srv.params)
    steady_s = time.perf_counter() - t0
    recompiled = meter.since(mark)[1]

    fused = cfg.backend == "pallas" and fused_sgd_eligible(model,
                                                          cfg.sampling)
    acc = float(hist["acc"][-1])
    finite = bool(np.isfinite(flat(params)).all())
    print(f"[{label}] local_sgd="
          f"{'pallas fused kernel' if fused else 'xla autodiff'} "
          f"compile_s={compile_s:.2f} programs={programs} cache_hits={hits} "
          f"first_run_s={first_s:.2f} steady_block_s={steady_s:.4f} "
          f"recompiles_in_steady_block={recompiled} acc={acc:.4f} "
          f"dropout={np.nanmean(hist['dropout']):.4f} finite={finite}",
          flush=True)
    require(finite, f"{label}: non-finite params")
    require(acc >= MIN_ACC, f"{label}: accuracy {acc} below {MIN_ACC}")
    require(recompiled == 0, f"{label}: the steady block recompiled")
    return srv, params, hist


def same_cohorts(a, b) -> bool:
    import numpy as np
    return bool(np.array_equal(np.stack(a.cohorts[:ROUNDS]),
                               np.stack(b.cohorts[:ROUNDS])))


def compress_parity(seed: int):
    """fed_compress on the chip against its reference twin on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    K, P = 30, 7850
    k = -(-P // 10)
    ef = np.random.default_rng(seed).normal(size=(K, P)).astype(np.float32)
    q, s = jax.device_get(ops.fed_compress_topk_q8(jnp.asarray(ef), k))
    with jax.default_device(jax.devices("cpu")[0]):
        qr, sr = jax.device_get(ref.fed_compress_topk_q8(jnp.asarray(ef),
                                                         k=k))
    kept = bool(np.array_equal(q != 0, qr != 0))
    codes = int((q != qr).sum())
    return kept, codes, bool(np.array_equal(s, sr))


def reference_round(srv, ds, params, backend: str, seed: int):
    """One round on the chip vs the plain padded round on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import RoundEngine, budget_iters
    ids = np.asarray(srv.cohorts[-1], np.int32)
    n = np.minimum(ds.sizes[ids], srv.max_n)
    n_iters = np.asarray(budget_iters(srv.H[ids], n, srv.cfg.batch_size,
                                      srv.max_iters))
    key = jax.random.PRNGKey(seed + 11)
    eng = RoundEngine(lr=srv.cfg.lr, donate=False)
    pk = srv.packed
    chip = eng.make_packed_round(srv.model, srv.cfg.batch_size,
                                 srv.max_iters, srv.max_n, sampling="iid",
                                 backend=backend)
    p_chip, l_chip, _ = jax.device_get(chip(
        params, pk.x, pk.y, pk.offsets, pk.lengths, jnp.asarray(ids),
        jnp.asarray(n_iters), key))

    padded = eng.make_padded_round(srv.model, srv.cfg.batch_size,
                                   srv.max_iters, sampling="iid",
                                   backend="xla")
    x, y, mask, nk = ds.stacked(ids, srv.max_n)
    cpu_args = jax.device_put(
        (params, x, y, mask, nk.astype(np.int32), n_iters, key),
        jax.devices("cpu")[0])
    p_ref, l_ref, _ = jax.device_get(padded(*cpu_args))
    rel, mx = deviation(p_chip, p_ref)
    loss_dev = float(np.abs(np.asarray(l_chip) - np.asarray(l_ref)).max())
    return rel, mx, loss_dev, int((n_iters > 0).sum())


def one_chip(ds, seed, meter):
    import jax
    srv_x, p_x, _ = train("xla", ds, paper_config("xla", seed), meter)

    srv_p, p_p, _ = train("pallas", ds, paper_config("pallas", seed), meter)
    kernels = segment_kernels(srv_p)
    rel, mx = deviation(p_p, p_x)
    cohorts = same_cohorts(srv_p, srv_x)
    print(f"[pallas] tpu_custom_call_sites={kernels} same_cohorts={cohorts} "
          f"vs_xla_rel_l2={rel:.3e} vs_xla_max_abs={mx:.3e} "
          f"tolerance_rel_l2={RUN_RTOL:.3e}", flush=True)
    require(kernels >= 2, "pallas segment lacks the gather/SGD kernels")
    require(cohorts, "pallas and xla runs drew different cohorts")
    require(rel <= RUN_RTOL, "pallas params deviate from the xla run")

    srv_c, _, _ = train("compressed", ds,
                        paper_config("pallas", seed,
                                     upload_compress="topk_q8"), meter)
    kernels = segment_kernels(srv_c)
    kept, codes, scales = compress_parity(seed)
    print(f"[compressed] tpu_custom_call_sites={kernels} "
          f"kernel_vs_ref_same_kept_coords={kept} "
          f"kernel_vs_ref_codes_differing={codes} "
          f"kernel_vs_ref_scales_equal={scales}", flush=True)
    require(kernels >= 3, "compressed segment lacks the fed_compress kernel")
    require(kept and scales and codes == 0,
            "fed_compress differs from its reference twin")

    for backend in ("xla", "pallas"):
        rel, mx, loss_dev, uploads = reference_round(srv_x, ds, p_x,
                                                     backend, seed)
        print(f"[reference] chip={backend} vs cpu padded round: "
              f"clients_training={uploads} rel_l2={rel:.3e} "
              f"max_abs={mx:.3e} max_loss_dev={loss_dev:.3e} "
              f"tolerance_rel_l2={ROUND_RTOL:.3e}", flush=True)
        require(rel <= ROUND_RTOL,
                f"{backend} round deviates from the CPU reference")
    return jax.devices()


def four_chips(ds, seed, meter):
    import jax
    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, "
            f"found {len(devices)}")
    srv_r, p_r, _ = train("replicated", ds, paper_config("xla", seed), meter)
    srv_s, p_s, hist = train(
        "sharded", ds, paper_config("xla", seed, mesh_shards=4,
                                    cohort_capacity="auto"), meter)
    shards = {s.device.id: s.data.shape
              for s in srv_s.packed.x.addressable_shards}
    overflowed = float(sum(hist["overflowed"]))
    rel, mx = deviation(p_s, p_r)
    bitwise = bool((flat(p_s) == flat(p_r)).all())
    print(f"[sharded] capacity={srv_s.capacity} client_blocks_by_device="
          f"{shards} overflowed={overflowed:.0f} "
          f"same_cohorts={same_cohorts(srv_s, srv_r)} "
          f"vs_replicated_rel_l2={rel:.3e} max_abs={mx:.3e} "
          f"bitwise={bitwise} tolerance_rel_l2={ROUND_RTOL:.3e}",
          flush=True)
    for d in devices[:4]:
        stats = d.memory_stats() or {}
        print(f"[sharded] device={d.id} bytes_in_use="
              f"{stats.get('bytes_in_use')} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}", flush=True)
    require(len(shards) == 4, "client blocks are not on four devices")
    require(overflowed == 0, "capacity overflow: runs are not comparable")
    require(rel <= ROUND_RTOL, "sharded params deviate from replicated")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: every one-chip phase; 4: only the sharded "
                         "path and the replicated run it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the federation, the params and every draw")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the reference phase runs on this process's CPU device
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: the default device is not a TPU", file=sys.stderr)
        return 1

    from repro.data import make_mnist_like
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    meter = CompileMeter(jax)
    t0 = time.perf_counter()
    ds = make_mnist_like(seed=args.seed)
    print(f"[data] clients={ds.n_clients} samples={int(ds.sizes.sum())} "
          f"max_size={int(ds.sizes.max())} dim={ds.clients_x[0].shape[1]} "
          f"setup_s={time.perf_counter() - t0:.2f} compile_cache={cache} "
          f"cache_entries_at_start={cached}", flush=True)

    phases = four_chips if args.chips == 4 else one_chip
    devices = phases(ds, args.seed, meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
