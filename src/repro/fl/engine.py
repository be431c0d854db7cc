"""Federation engines: who runs the M local SGD steps for a set of clients.

``LegacyEngine`` is the seed implementation — a Python loop over
``FLClient.local_train``, one jit cache per client, ``local_steps`` host
round-trips per client per round. Kept as the reference for equivalence
tests and as the slow baseline in ``benchmarks/fl_engine_bench.py``.

``BatchedEngine`` is the scaled implementation: the whole federation's
data lives device-resident as padded ``(K, n_max, ...)`` arrays
(``repro.data.pipeline.stack_federation``), and one jitted function runs
``lax.scan`` over the M local steps inside ``jax.vmap`` over the K
clients. One compilation covers every round at every participation
pattern; the per-round host work is only the numpy batch-index planning.

Determinism/equivalence contract: both engines draw minibatch indices
from the same stateful ``ClientData.batch_indices`` stream, so with equal
seeds they train on identical sample sequences and produce global models
equal up to float-reduction reordering (verified by
tests/test_engine_equivalence.py with ``allclose``).

``BatchedEngine.enable_counter_plan`` switches to the third planning mode:
stateless counter-based ``jax.random`` plans (``repro.data.pipeline
.counter_batch_plan``) keyed on the broadcast round. This is the mode the
fused on-device round scans with — and the mode the host-path server runs
in when it serves as the fused path's reference (PAOTAConfig.rng
= "counter").

Masking semantics for a partial broadcast (only ``ids`` restart): the
batched call still executes the fused K-client computation — clients
outside ``ids`` get an all-zeros index plan and their (discarded) output
row is never read; their epoch cursors do not advance. Padding rows of
ragged clients are never gathered because index plans are drawn from
``range(n_k)`` only.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.scheduler import TAG_BATCH, round_tag_key
from repro.data.pipeline import ClientData, counter_batch_plan, stack_federation
from repro.fl.client import FLClient


def ravel_rows(stacked):
    """(K, d) raveled rows of a client-stacked params pytree: each (K, ...)
    leaf reshaped to (K, d_leaf), concatenated in tree_flatten order."""
    leaves = jax.tree_util.tree_leaves(stacked)
    if len(leaves) == 1:
        return leaves[0].reshape((leaves[0].shape[0], -1))
    return jnp.concatenate(
        [l.reshape((l.shape[0], -1)) for l in leaves], axis=1)


class LegacyEngine:
    """Reference engine: per-client Python loop (the seed behaviour)."""

    name = "legacy"

    def __init__(self, clients: List[FLClient]):
        self.clients = clients
        self.n_clients = len(clients)
        self.n_samples = np.array([c.n_samples for c in clients], np.int64)

    def local_train(self, params, ids: Sequence[int],
                    round_idx=None) -> np.ndarray:
        """Train clients `ids` from `params`; returns (len(ids), d) raveled
        trained models, rows ordered as `ids`. An empty broadcast returns
        shape (0, d) — the model dimension is preserved so callers can
        concatenate without special-casing. ``round_idx`` is accepted for
        interface parity with the batched engine and ignored (the legacy
        loop only supports the stateful host-cursor plans)."""
        out = []
        for k in ids:
            trained = self.clients[int(k)].local_train(params)
            tv, _ = ravel_pytree(trained)
            out.append(np.asarray(tv))
        if not out:
            d = int(ravel_pytree(params)[0].size)
            return np.zeros((0, d))
        return np.stack(out)


class BatchedEngine:
    """vmap-over-clients, scan-over-steps engine: one compile per federation."""

    name = "batched"

    def __init__(self, fed: List[ClientData], loss_fn, batch_size: int = 32,
                 lr: float = 0.05, local_steps: int = 5):
        self.fed = fed  # epoch cursors (host-side batch planning) live here
        self.loss_fn = loss_fn
        self.batch_size = batch_size
        self.lr = lr
        self.local_steps = local_steps
        self.n_clients = len(fed)
        stacked = stack_federation(fed)
        self.n_samples = stacked.n_samples
        # NOTE: n_k >= batch_size is a restriction of the HOST epoch-cursor
        # planner only (it slices fixed windows from the epoch permutation)
        # and is enforced at first host-plan use — counter plans draw
        # bounded by n_k, so short-batch clients federate fine there
        self._x = jnp.asarray(stacked.x)
        self._y = jnp.asarray(stacked.y)
        self._n_dev = jnp.asarray(self.n_samples, jnp.int32)
        self._idx = np.zeros((self.n_clients, local_steps, batch_size),
                             np.int32)
        self._train = jax.jit(self._train_all)
        # stateless counter-based planning (enable_counter_plan): index
        # plans become a pure function of (plan key, round) — required by
        # the fused round and by the host reference compared against it
        self.plan = "host"
        self._plan_key = None
        # optional per-client hyperparameter heterogeneity: (K,) arrays
        # installed by set_heterogeneity (None = homogeneous — the exact
        # historical program)
        self._steps_k = None
        self._batch_k = None

    @classmethod
    def from_clients(cls, clients: List[FLClient]) -> "BatchedEngine":
        """Build from a homogeneous FLClient list (same hyperparameters)."""
        c0 = clients[0]
        for c in clients[1:]:
            if (c.loss_fn is not c0.loss_fn or c.batch_size != c0.batch_size
                    or c.lr != c0.lr or c.local_steps != c0.local_steps):
                raise ValueError("BatchedEngine requires homogeneous client "
                                 "hyperparameters; got a mixed federation")
        return cls([c.data for c in clients], c0.loss_fn,
                   batch_size=c0.batch_size, lr=c0.lr,
                   local_steps=c0.local_steps)

    # ------------------------------------------------------------------
    def set_heterogeneity(self, steps_k=None, batch_k=None) -> None:
        """Install per-client (K,) hyperparameter heterogeneity: local-step
        counts (1 <= steps_k <= local_steps — extra plan rows become no-op
        steps via a zeroed step size) and/or batch sizes (1 <= batch_k <=
        batch_size — the counter plan repeats each client's first b_k
        draws cyclically across the fixed-width row, so the averaged
        gradient is EXACTLY the b_k-minibatch gradient whenever b_k
        divides batch_size). None leaves a dimension homogeneous. The
        fused/sharded drivers install these from ``ScenarioConfig
        .het_steps`` / ``.het_batch``."""
        if steps_k is not None:
            s = np.asarray(steps_k)
            if s.shape != (self.n_clients,):
                raise ValueError(f"steps_k shape {s.shape} != "
                                 f"({self.n_clients},)")
            if s.min() < 1 or s.max() > self.local_steps:
                raise ValueError(f"steps_k must lie in [1, local_steps="
                                 f"{self.local_steps}]; got "
                                 f"[{int(s.min())}, {int(s.max())}]")
            steps_k = jnp.asarray(s, jnp.int32)
        if batch_k is not None:
            bks = np.asarray(batch_k)
            if bks.shape != (self.n_clients,):
                raise ValueError(f"batch_k shape {bks.shape} != "
                                 f"({self.n_clients},)")
            if bks.min() < 1 or bks.max() > self.batch_size:
                raise ValueError(f"batch_k must lie in [1, batch_size="
                                 f"{self.batch_size}]; got "
                                 f"[{int(bks.min())}, {int(bks.max())}]")
            batch_k = jnp.asarray(bks, jnp.int32)
        self._steps_k = steps_k
        self._batch_k = batch_k

    def steps_for(self, client_ids=None):
        """This federation's (K,) per-client step counts gathered at
        ``client_ids`` (None = all rows; returns None when homogeneous) —
        the form ``_train_all``/``_train_all_tree`` consume."""
        if self._steps_k is None:
            return None
        if client_ids is None:
            return self._steps_k
        return self._steps_k[jnp.asarray(client_ids, jnp.int32)]

    def _sgd(self, params, inputs, batch_of, n_steps=None):
        """M local SGD steps from the broadcast ``params`` pytree, one per
        leading row of ``inputs`` (a pytree of (M, ...) leaves);
        ``batch_of`` maps one row to the step's ``{"x", "y"}`` batch.
        Returns the trained params pytree (no ravel).

        ``n_steps`` (traced scalar) masks heterogeneous step counts: rows
        at positions >= n_steps multiply their gradient by an exactly
        zero step size, so ``pp - 0 * gg == pp`` bit for bit and a client
        with n_steps = s equals the s-step homogeneous run on the same
        plan rows. ``None`` keeps the historical unmasked program."""
        def step(p, inp):
            g = jax.grad(self.loss_fn)(p, batch_of(inp))
            return jax.tree_util.tree_map(
                lambda pp, gg: pp - self.lr * gg, p, g), None

        def masked_step(p, inp):
            inp, i = inp
            g = jax.grad(self.loss_fn)(p, batch_of(inp))
            lr = jnp.float32(self.lr) * (i < n_steps)
            return jax.tree_util.tree_map(
                lambda pp, gg: pp - lr * gg, p, g), None
        # M is small (a handful of local steps): full unroll lets XLA
        # fuse across steps instead of paying while-loop overhead
        if n_steps is None:
            p, _ = jax.lax.scan(step, params, inputs, unroll=True)
        else:
            m_steps = jax.tree_util.tree_leaves(inputs)[0].shape[0]
            pos = jnp.arange(m_steps, dtype=jnp.int32)
            p, _ = jax.lax.scan(masked_step, params, (inputs, pos),
                                unroll=True)
        return p

    def _train_one(self, params, xc, yc, plan, n_steps=None):
        """One client's M local SGD steps on its own (n_max, ...) data
        rows: each step gathers its minibatch ``xc[sel]`` from the plan
        row ``sel``."""
        return self._sgd(params, plan,
                         lambda sel: {"x": xc[sel], "y": yc[sel]}, n_steps)

    def _train_all(self, params, x, y, idx, n_steps=None):
        """params: pytree of (…) broadcast to every client; x/y: padded
        (K, n_max, …) data; idx: (K, M, B) minibatch plans; ``n_steps``:
        optional (K,) heterogeneous step counts. Returns (K, d) raveled
        trained models.

        The ravel happens ONCE on the stacked result — reshape each
        (K, ...) leaf to (K, d_leaf) and concatenate in tree_flatten
        order — which is value-identical to ``ravel_pytree`` per client
        (same leaf order, same row-major ravel) but costs one (K, d)
        write instead of a vmapped per-client concatenate (~40% of the
        train call at transformer-scale d)."""
        return ravel_rows(self._train_all_tree(params, x, y, idx, n_steps))

    def _train_all_tree(self, params, x, y, idx, n_steps=None):
        """Pytree twin of ``_train_all``: same local SGD, but the trained
        models come back as a client-stacked params pytree ((K, ...)
        leaves) instead of a raveled (K, d) matrix — the form the
        pytree-native round core carries (repro.fl.runtime)."""
        if n_steps is None:
            return jax.vmap(
                lambda xc, yc, plan: self._train_one(params, xc, yc, plan)
            )(x, y, idx)
        return jax.vmap(
            lambda xc, yc, plan, ns: self._train_one(params, xc, yc, plan,
                                                     ns)
        )(x, y, idx, n_steps)

    def _train_gathered(self, params, x, y, ids, idx, n_steps=None):
        """Cohort training: the clients at rows ``ids`` (m,) of the
        padded (K, n_max, ...) data train from ``params`` on their (m, M,
        B) minibatch plans ``idx``; ``n_steps``: optional (m,) step
        counts. Returns the (m, ...) client-stacked params pytree.

        Every minibatch of every slot comes out of the plane in ONE
        gather, ``x[ids, idx]``: m x M x B sample rows. Gathering the
        slots' whole client rows first (``x[ids]``, then ``xc[sel]`` per
        step) reads the same samples, but on a TPU it lowered to a
        relayout copy of the whole plane and a slice of every row in
        each round."""
        rows = ids[:, None, None]
        batches = {"x": x[rows, idx], "y": y[rows, idx]}
        # n_steps None is an empty pytree to vmap: the unmasked program
        return jax.vmap(lambda b, ns: self._sgd(params, b, lambda s: s, ns)
                        )(batches, n_steps)

    def enable_counter_plan(self, key) -> None:
        """Switch minibatch planning to the stateless counter scheme: the
        (K, M, B) plan for broadcast round r is ``counter_batch_plan``
        keyed on round_tag_key(key, r, TAG_BATCH). Epoch cursors in
        ``self.fed`` are no longer consumed."""
        self.plan = "counter"
        self._plan_key = key

    def round_plan(self, round_idx, client_ids=None, n_samples=None):
        """Counter-mode (K, M, B) index plan for broadcast round
        ``round_idx`` (host path and fused path call the same function).
        A mesh shard — or the active cohort — passes its ``client_ids``
        slice plus the matching ``n_samples`` rows and gets exactly its
        rows of the full plan (each client's draw depends only on the key
        and its own id/size). Heterogeneous batch sizes, when installed,
        gather by the same ids."""
        key = round_tag_key(self._plan_key, round_idx, TAG_BATCH)
        n = self._n_dev if n_samples is None else n_samples
        bs = None
        if self._batch_k is not None:
            bs = (self._batch_k if client_ids is None
                  else self._batch_k[jnp.asarray(client_ids, jnp.int32)])
        return counter_batch_plan(key, n, self.local_steps,
                                  self.batch_size, client_ids=client_ids,
                                  batch_sizes=bs)

    def _broadcast_plans(self, ids, round_idx):
        """(K, M, B) index plans for a broadcast of ``ids``: the full
        counter plan in counter mode, host epoch-cursor plans (zeros for
        non-broadcast rows) otherwise."""
        if self.plan == "counter":
            if round_idx is None:
                raise ValueError("counter-plan engine needs the broadcast "
                                 "round index")
            return self.round_plan(int(round_idx))
        if int(self.n_samples.min()) < self.batch_size:
            raise ValueError(
                f"host epoch-cursor plans need n_k >= batch_size for "
                f"fixed-shape minibatches (min n_k="
                f"{int(self.n_samples.min())}, batch_size="
                f"{self.batch_size}); use counter plans "
                f"(enable_counter_plan) or LegacyEngine for short-batch "
                f"clients")
        self._idx[:] = 0
        for k in ids:
            self._idx[k] = np.stack(list(
                self.fed[k].batch_indices(self.batch_size,
                                          self.local_steps)))
        return jnp.asarray(self._idx)

    def local_train_full(self, params, ids: Sequence[int],
                         round_idx=None) -> jnp.ndarray:
        """Device-resident full-federation training: the whole (K, d)
        trained stack stays on device with FIXED shapes — the host PAOTA
        server masks out the non-broadcast rows itself instead of
        gathering ``ids`` (a varying-length gather/scatter re-lowered a
        fresh XLA program for every distinct participation count, and the
        numpy round-trip was the measured host-reference ceiling at
        K ~ 10^4). Rows outside ``ids`` are untrained garbage (zero index
        plans / unconsumed counter rows) and MUST be masked by the
        caller."""
        ids = np.asarray(ids, np.int64)
        idx = self._broadcast_plans(ids, round_idx)
        return self._train(params, self._x, self._y, idx, self._steps_k)

    def local_train(self, params, ids: Sequence[int],
                    round_idx=None) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        flat = self.local_train_full(params, ids, round_idx=round_idx)
        # subset on device: only the requested rows cross to host
        return np.asarray(flat[jnp.asarray(ids)])


def make_engine(clients, kind: str = "batched"):
    """Engine factory used by the servers.

    `clients` may be an engine instance (returned unchanged), or a list of
    FLClient to wrap in the requested engine kind.
    """
    if hasattr(clients, "local_train") and hasattr(clients, "n_clients"):
        return clients
    if kind == "batched":
        return BatchedEngine.from_clients(list(clients))
    if kind == "legacy":
        return LegacyEngine(list(clients))
    raise ValueError(f"unknown engine kind: {kind!r} "
                     "(expected 'batched' or 'legacy')")
