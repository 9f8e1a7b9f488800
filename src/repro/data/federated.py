"""Federated dataset generators (offline stand-ins, DESIGN.md §5).

The container has no network access, so the LEAF datasets are replaced by
synthetic generators that match the paper's published *statistics*:

  MNIST-like     1,000 clients, 69,035 samples, 2 classes/client, power law
  FEMNIST-like     200 clients, 18,345 samples, 5 classes/client, 26 classes
  Synthetic(a,b)   100 clients, power law  — exact Shamir et al. generator
                   as used by LEAF / FedProx
  Sent140-like     772 clients, ~40,783 tweets, binary sentiment, token seqs

Each client k holds (x_k, y_k) numpy arrays; a shared IID test set evaluates
the global model each round, as in the paper.

The gathered per-client minibatch the engine feeds every ``LocalStep`` is
``{"x": [B, ...], "y": [B], "mask": [B]}`` — features (float for the
image-like tasks, int32 token sequences for sent140), labels, and sample
validity (padding rows are mask 0 and must contribute zero loss).  That
dict is the whole data-side contract a model has to speak (ISSUE 9);
``models.api.from_model`` adapts it to the causal-LM objective by deriving
inputs/targets from the token sequences.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

# Every client's first row in the packed arrays is a multiple of this: the
# f32 sublane tiling of a TPU, so the fed_gather kernel's per-client window
# starts on a tile boundary (kernels/fed_gather.py).
ROW_ALIGN = 8


def _align(n: int) -> int:
    return -(-n // ROW_ALIGN) * ROW_ALIGN


def _aligned_offsets(ns: np.ndarray) -> np.ndarray:
    """Row offsets of clients packed back to back, each rounded up to
    ``ROW_ALIGN`` (the gap rows are zeros)."""
    ends = np.cumsum([_align(int(n)) for n in ns], dtype=np.int64)
    return np.concatenate([[0], ends[:-1]]).astype(np.int64)


@dataclasses.dataclass
class PackedClients:
    """Device-resident flat federation (ISSUE 1): every client's samples
    concatenated into one array, addressed by per-client offset/length.

    Uploaded to device once (at server construction); the per-round cohort
    gather — ``x[offsets[ids, None] + arange(max_n)]`` — runs on device, so a
    round moves O(K) ids host->device instead of O(K * max_n * feature_dim)
    restacked padded samples.

    Each client starts on a ``ROW_ALIGN``-row boundary (zero rows fill the
    gaps), and ``x``/``y`` carry ``max_n`` rounded up to ``ROW_ALIGN`` zero
    rows of tail slack past the last client, so every client's aligned
    ``[offset, offset + max_n)`` window is in bounds — the contract the
    Pallas ``fed_gather`` kernel DMAs against (kernels/fed_gather.py).  Gap
    and slack rows are masked out of every statistic like any other
    padding.

    Sharded layout (ISSUE 4, ``packed(shards=S)``): every array gains a
    leading shard axis that maps onto the ``data`` mesh axis.  Shard ``s``
    owns the contiguous client block ``[s * C, (s + 1) * C)`` where
    ``C = clients_per_shard``, so global client ``g`` lives on shard
    ``g // C`` at local row ``g % C``.  Each shard's flat arrays hold only
    its own clients' samples (with the same alignment and tail slack, per
    shard, then zero-padding up to a common length so the shards
    stack); ``offsets`` are shard-local.  The last shard may own ghost
    clients (``lengths == 0``) when S does not divide the population —
    ghosts are never selected and gather nothing.
    """
    x: object         # jnp [rows, ...feat]  (sharded: [S, L, ...])
    y: object         # jnp [rows] int32     (sharded: [S, L])
    offsets: object   # jnp [n_clients] int32         (sharded: [S, C], local)
    lengths: object   # jnp [n_clients] int32         (sharded: [S, C])
    max_n: int        # cohort shard width; consumed by make_packed_round
    n_shards: int = 0          # 0 = unsharded flat layout
    clients_per_shard: int = 0  # C (sharded layouts only)

    def shard_to(self, mesh):
        """Place the shard axis on the mesh's ``data`` axis (one-time
        device_put; the logical->physical mapping goes through the shared
        ``sharding.rules`` table, same as the transformer stack)."""
        import jax
        from jax.sharding import NamedSharding

        from repro.sharding.rules import logical_spec

        if not self.n_shards:
            raise ValueError("shard_to() requires a sharded layout "
                             "(FederatedDataset.packed(shards=S))")
        mesh_shards = mesh.shape["data"]
        if self.n_shards != mesh_shards:
            # a divisible mismatch would otherwise pass every sharding
            # check and silently drop whole client blocks in the engine
            raise ValueError(
                f"layout has {self.n_shards} shards but the mesh data axis "
                f"has {mesh_shards} devices; repack with shards="
                f"{mesh_shards}")

        def put(a):
            spec = logical_spec(a.shape, ("clients",) + (None,) * (a.ndim - 1),
                                mesh=mesh)
            return jax.device_put(a, NamedSharding(mesh, spec))

        return dataclasses.replace(
            self, x=put(self.x), y=put(self.y), offsets=put(self.offsets),
            lengths=put(self.lengths))


@dataclasses.dataclass
class FederatedDataset:
    name: str
    clients_x: List[np.ndarray]
    clients_y: List[np.ndarray]
    test_x: np.ndarray
    test_y: np.ndarray
    n_classes: int
    task: str = "classification"   # classification | text

    @property
    def n_clients(self) -> int:
        return len(self.clients_x)

    @property
    def sizes(self) -> np.ndarray:
        return np.array([len(y) for y in self.clients_y])

    def stacked(self, client_ids, max_n: Optional[int] = None):
        """Gather selected clients into padded arrays for the vmapped round.

        Returns (x [K, max_n, ...], y [K, max_n], mask [K, max_n], n [K]).
        """
        ids = list(client_ids)
        ns = np.array([len(self.clients_y[i]) for i in ids])
        m = int(max_n or ns.max())
        feat_shape = self.clients_x[ids[0]].shape[1:]
        x = np.zeros((len(ids), m) + feat_shape, self.clients_x[ids[0]].dtype)
        y = np.zeros((len(ids), m), np.int32)
        mask = np.zeros((len(ids), m), np.float32)
        for j, i in enumerate(ids):
            n = min(len(self.clients_y[i]), m)
            x[j, :n] = self.clients_x[i][:n]
            y[j, :n] = self.clients_y[i][:n]
            mask[j, :n] = 1.0
        return x, y, mask, np.minimum(ns, m)

    def packed(self, max_n: Optional[int] = None,
               shards: Optional[int] = None) -> PackedClients:
        """One-time device upload of the whole federation (see PackedClients).

        ``max_n`` bounds the per-round cohort shard width (defaults to the
        largest client), mirroring ``stacked``'s padding width.

        ``shards`` (ISSUE 4) selects the sharded layout: clients are split
        into ``shards`` contiguous blocks of ``C = ceil(N / shards)``
        (ghost-padded with empty clients when the population does not
        divide), each block's samples concatenated into its own flat array
        with the same ``max_n`` tail-slack contract, all blocks zero-padded
        to a common flat length so the arrays stack [S, L, ...].
        """
        import jax.numpy as jnp  # lazy: generators stay importable sans jax

        ns = self.sizes
        m = int(max_n or ns.max())
        if shards:
            return self._packed_sharded(int(shards), m)
        offsets = _aligned_offsets(ns)
        # aligned tail slack: every per-client [offset, offset+max_n)
        # window stays in bounds (the fed_gather DMA contract)
        rows = int(offsets[-1]) + _align(int(ns[-1])) + _align(m)
        x = np.zeros((rows,) + self.clients_x[0].shape[1:],
                     self.clients_x[0].dtype)
        y = np.zeros(rows, np.int32)
        for off, cx, cy in zip(offsets, self.clients_x, self.clients_y):
            x[off:off + len(cy)] = cx
            y[off:off + len(cy)] = cy
        return PackedClients(
            x=jnp.asarray(x), y=jnp.asarray(y),
            offsets=jnp.asarray(offsets, jnp.int32),
            lengths=jnp.asarray(ns, jnp.int32),
            max_n=m)

    def _packed_sharded(self, shards: int, max_n: int) -> PackedClients:
        import jax.numpy as jnp

        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        N = self.n_clients
        C = -(-N // shards)                       # ceil: ghost-pad the tail
        ns = self.sizes
        feat = self.clients_x[0].shape[1:]
        dtype = self.clients_x[0].dtype
        # common flat length: widest shard's aligned samples + tail slack
        blocks = [list(range(s * C, min((s + 1) * C, N)))
                  for s in range(shards)]
        L = max(sum(_align(int(ns[g])) for g in b) for b in blocks) \
            + _align(max_n)
        x = np.zeros((shards, L) + feat, dtype)
        y = np.zeros((shards, L), np.int32)
        offsets = np.zeros((shards, C), np.int32)
        lengths = np.zeros((shards, C), np.int32)
        for s, block in enumerate(blocks):
            if not block:
                continue
            offs = _aligned_offsets(ns[block])
            for j, (g, pos) in enumerate(zip(block, offs)):
                n = len(self.clients_y[g])
                offsets[s, j] = pos
                lengths[s, j] = n
                x[s, pos:pos + n] = self.clients_x[g]
                y[s, pos:pos + n] = self.clients_y[g]
        return PackedClients(
            x=jnp.asarray(x), y=jnp.asarray(y),
            offsets=jnp.asarray(offsets), lengths=jnp.asarray(lengths),
            max_n=max_n, n_shards=shards, clients_per_shard=C)


def power_law_sizes(rng: np.random.Generator, n_clients: int, total: int,
                    alpha: float = 1.6, min_size: int = 10,
                    max_size: int = 0) -> np.ndarray:
    """Per-client sample counts following a power law, summing ~= total."""
    raw = rng.pareto(alpha, n_clients) + 1.0
    sizes = raw / raw.sum() * (total - min_size * n_clients)
    sizes = (sizes + min_size).astype(int)
    if max_size:
        sizes = np.minimum(sizes, max_size)
    return np.maximum(sizes, min_size)


def _clustered_classification(rng, n_clients, total, n_classes,
                              classes_per_client, dim, sep, noise,
                              max_size=0, test_n=2000):
    """Gaussian class clusters in R^dim; label-skewed client partitions."""
    protos = rng.normal(0, sep, (n_classes, dim)).astype(np.float32)
    sizes = power_law_sizes(rng, n_clients, total, max_size=max_size)
    xs, ys = [], []
    for k in range(n_clients):
        classes = rng.choice(n_classes, classes_per_client, replace=False)
        y = rng.choice(classes, sizes[k]).astype(np.int32)
        x = protos[y] + rng.normal(0, noise, (sizes[k], dim)).astype(np.float32)
        xs.append(x.astype(np.float32))
        ys.append(y)
    ty = rng.integers(0, n_classes, test_n).astype(np.int32)
    tx = protos[ty] + rng.normal(0, noise, (test_n, dim)).astype(np.float32)
    return xs, ys, tx, ty


def make_mnist_like(seed: int = 0, n_clients: int = 1000, total: int = 69035,
                    dim: int = 784, max_size: int = 400, sep: float = 1.0,
                    noise: float = 1.2) -> FederatedDataset:
    """Paper stats: 1,000 devices, 69,035 samples, 2 classes/device."""
    rng = np.random.default_rng(seed)
    xs, ys, tx, ty = _clustered_classification(
        rng, n_clients, total, n_classes=10, classes_per_client=2,
        dim=dim, sep=sep, noise=noise, max_size=max_size)
    return FederatedDataset("mnist", xs, ys, tx, ty, 10)


def make_femnist_like(seed: int = 0, n_clients: int = 200, total: int = 18345,
                      dim: int = 784, max_size: int = 400) -> FederatedDataset:
    """Paper stats: 200 devices, 18,345 samples, 5 classes/device, 26-class."""
    rng = np.random.default_rng(seed + 1)
    xs, ys, tx, ty = _clustered_classification(
        rng, n_clients, total, n_classes=26, classes_per_client=5,
        dim=dim, sep=0.8, noise=1.4, max_size=max_size)
    return FederatedDataset("femnist", xs, ys, tx, ty, 26)


def make_synthetic(alpha: float = 1.0, beta: float = 1.0, seed: int = 0,
                   n_clients: int = 100, dim: int = 60, n_classes: int = 10,
                   total: int = 75349, max_size: int = 2000) -> FederatedDataset:
    """Synthetic(alpha, beta) — the Shamir et al. generator (LEAF/FedProx).

    alpha controls how much local models differ; beta how much local data
    distributions differ.  Paper uses Synthetic(1,1), 100 devices.
    """
    rng = np.random.default_rng(seed + 2)
    sizes = power_law_sizes(rng, n_clients, total, max_size=max_size)
    diag = np.array([(j + 1) ** -1.2 for j in range(dim)])
    xs, ys = [], []
    test_x, test_y = [], []
    for k in range(n_clients):
        u_k = rng.normal(0, alpha)
        b_k = rng.normal(0, beta)
        v_k = rng.normal(b_k, 1.0, dim)
        W = rng.normal(u_k, 1.0, (dim, n_classes))
        b = rng.normal(u_k, 1.0, n_classes)
        n = sizes[k] + 20
        x = rng.normal(v_k, 1.0, (n, dim)) * np.sqrt(diag)
        logits = x @ W + b
        y = np.argmax(logits, axis=-1).astype(np.int32)
        xs.append(x[:sizes[k]].astype(np.float32))
        ys.append(y[:sizes[k]])
        test_x.append(x[sizes[k]:].astype(np.float32))
        test_y.append(y[sizes[k]:])
    return FederatedDataset("synthetic(1,1)", xs, ys,
                            np.concatenate(test_x), np.concatenate(test_y),
                            n_classes)


def make_sent140_like(seed: int = 0, n_clients: int = 772, total: int = 40783,
                      vocab: int = 1000, seq_len: int = 25,
                      max_size: int = 300) -> FederatedDataset:
    """Binary sentiment over token sequences; 5 polarity tokens per tweet."""
    rng = np.random.default_rng(seed + 3)
    sizes = power_law_sizes(rng, n_clients, total, max_size=max_size)
    pos_tokens = np.arange(0, 100)
    neg_tokens = np.arange(100, 200)

    def tweets(n, labels):
        x = rng.integers(200, vocab, (n, seq_len)).astype(np.int32)
        n_sent = rng.integers(3, 8, n)
        for i in range(n):
            pool = pos_tokens if labels[i] == 1 else neg_tokens
            pos = rng.choice(seq_len, n_sent[i], replace=False)
            x[i, pos] = rng.choice(pool, n_sent[i])
        return x

    xs, ys = [], []
    for k in range(n_clients):
        y = rng.integers(0, 2, sizes[k]).astype(np.int32)
        xs.append(tweets(sizes[k], y))
        ys.append(y)
    ty = rng.integers(0, 2, 2000).astype(np.int32)
    tx = tweets(2000, ty)
    return FederatedDataset("sent140", xs, ys, tx, ty, 2, task="text")


DATASETS = {
    "mnist": make_mnist_like,
    "femnist": make_femnist_like,
    "synthetic": make_synthetic,
    "sent140": make_sent140_like,
}
