"""Pallas kernel layer: compute hot-spots with custom TPU kernels.

Layout convention: one ``<name>.py`` per kernel family (the raw
``pallas_call`` machinery), ``ops.py`` for the jit'd public entry points,
``ref.py`` for the pure-jnp oracles every kernel is validated against.
The platform decides how a kernel runs: on a TPU the ``ops`` wrappers
compile every ``pallas_call`` with Mosaic, on any other backend they run it
in the Pallas interpreter (chosen at trace time, see ``ops``).

Model kernels (custom_vjp, backward recomputes through the oracle):

  flash_attention.py  online-softmax attention fwd/bwd, causal/window/GQA
  selective_scan.py   SSM recurrence (Mamba-style selective scan)
  fused_xent.py       fused softmax cross-entropy

Federated kernels (ISSUE 2) — the ``RoundEngine`` compute backend,
forward-only (round functions are never differentiated through):

  fed_gather.py       fused cohort gather+mask: per-client offsets arrive
                      via scalar prefetch, each grid step DMAs one client's
                      [max_n, feat] window from the packed federation in
                      HBM and writes the validity mask in-registers — no
                      [K, max_n] index tensor, no clamp-gather intermediate
  fed_local_sgd.py    fused masked budgeted local SGD for the paper's MCLR
                      model: all ``max_iters`` slots for a client run in one
                      grid step with the params held in VMEM scratch
                      (heterogeneous FedSAE budgets stay uniform control
                      flow via the ``i < n_iters_k`` update mask)

Select the kernel path with ``backend="pallas"`` on
``RoundEngine.make_packed_round`` / ``make_padded_round`` (plumbed through
``ServerConfig.backend`` and ``launch/fl_train.py --backend``; default
``"xla"``).  The flag is accepted by every scenario: stages with no
applicable kernel (non-MCLR models, ``sampling="shuffle"`` local SGD, silo
streams) fall back to the XLA implementation automatically, so flipping the
flag is always safe.
"""
