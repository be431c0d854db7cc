"""Mesh-sharded PAOTA: the fused round scanned under ``jax.shard_map``
over the mesh client axis.

``FusedPAOTA`` runs the whole aggregation period as one device call — but
on ONE device: a K = 10^4..10^5 federation serializes through a single
chip while the rest of the mesh idles. ``ShardedPAOTA`` lays the round
core's (K,) / (K, ...) carry rows and the engine's padded (K, n_max, ...)
federation over the mesh client axis (``repro.launch.mesh.data_axes`` /
``client_axes_for``; specs from ``repro.sharding.rules``) and runs the
SAME ``repro.fl.runtime`` scan inside ``shard_map``:

* per-client stages — local SGD (vmap over this shard's clients),
  latency/scheduler state, channel draw, eq.-25 factors, power cap (7) —
  are embarrassingly parallel: zero collectives;
* the AirComp superposition is ONE psum over the client axis per round
  (``repro.kernels.aircomp_sum``: the raveled form psums the flat
  accumulator, the pytree form concatenates per-leaf partials and psums
  once — never per leaf), plus the water-filling P2 grid reductions and
  the round metrics (a handful of scalar psums).

Params modes (``params_mode``): ``"raveled"`` federates the flat (K, d)
stack exactly as before; ``"pytree"`` carries the params pytree natively,
each client-stacked leaf placed by ``repro.sharding.rules
.stack_client_specs`` under the mesh client axes — so a transformer-config
client federation (e.g. a minicpm-class reduced config) runs full sharded
PAOTA rounds with its params in their natural structure.

Intra-client TP (``tp_axes``, pytree mode): on a ("pod", "data", "tp")
mesh (``make_pod_mesh(..., tp=N)``) each stacked payload leaf additionally
TP-shards one trailing dim over the TP axis — per-device model-plane
bytes drop ~1/TP, the wall that caps how large a single client can be.
Storage-parallel, compute-replicated: globals and local training stay
replicated over TP (full leaves everywhere), the stats sweep closes with
one small psum over the TP axes, the AirComp superposition stays ONE
model-sized psum (now spanning clients x TP — superpose and TP-gather in
the same collective), the AWGN is drawn at FULL shapes from the
replicated key (identical realization for every TP layout), and the
carry writes slice trained rows to the TP-local block
(``repro.sharding.tp``). TP extent 1 passes ``tp=None`` into the round —
op-for-op, bit-identical to the flat program. Any OTHER non-client mesh
axis with extent > 1 still refuses in pytree mode (name it in
``tp_axes`` — or ``client_axes`` — to use it).

Phantom-client padding: a client-axis extent that does not divide K no
longer refuses — the federation pads to the next multiple with masked
phantom clients whose ready bits are pinned False forever (busy_lat =
+inf, zero data rows, zero power). Phantoms never upload, never
broadcast, and carry b_k = 0 through every psum and metric, so the padded
trajectory equals the unpadded single-device one draw for draw
(tests/test_pytree_round.py).

Grouped aggregation (``group_period`` N >= 1, Air-FedGA style): the
client axes split into POD axes and INTRA-pod axes (``pod_axes``;
default: the first client axis indexes the pods). Every period each pod
superposes its own clients with an intra-pod psum and accumulates the
staleness-weighted partial into the carry's ``held`` slot; the cross-pod
psum — the only model-sized collective that leaves a pod — fires once
every N periods, at the window sync (``repro.fl.runtime.scan_windows``
unrolls the window inside the scan step so the compiled scan body holds
exactly ONE such all-reduce; benchmarks/grouped_round_bench.py counts
them in the HLO). ``group_period=1`` makes every period a sync with a
zero ``held``, which is op-for-op the flat program — grouped N=1 equals
flat bit-for-bit (tests/test_grouped_round.py).

Active-cohort mode (``cohort_size=m``): the slots split shard-LOCAL —
``m`` must tile the client shards, each shard runs the cohort round over
its ``m / n_shards`` slots and refills them from its OWN idle clients by
the shared counter-RNG priority draw (phantom rows are pinned to -inf and
can never win a slot). Slot refill order is therefore per-shard rather
than the fused driver's global priority order — a documented scheduling
POLICY difference (same distributions; at m = K both pin every client to
a permanent slot and the paths coincide). Round-0 cohort init also runs
inside ``shard_map``: its payload gathers use shard-local slot ids, which
plain GSPMD jit would misread as global rows. Grouped aggregation does
not compose with cohort mode yet. Compressed payloads (``compress=``)
shard the (m, s) slot planes and (K, s) parked EF residuals over the
same client axis; the randmask support is re-derived replicated on every
shard from the counter stream (no collective), the int8 dither key folds
in the shard offset, and the compressed superposition is still ONE psum
(``gather_superpose_psum`` concatenates the accumulator with the
varsigma partial).

Equivalence contract: every shard consumes its rows of the SAME global
counter-RNG draws the single-device scan makes — latency and channel
vectors are drawn full-K from the replicated round key, padded with
phantom fill, and sliced by shard offset; minibatch plans fold in GLOBAL
client ids (``counter_batch_plan(client_ids=...)``); the AWGN realization
is drawn once from the replicated noise key. The sharded trajectory is
therefore allclose to ``FusedPAOTA`` round for round (float reduction
order across shards is the only difference; zero-uploader periods hold
w_g bit-identically on every shard) — tests/test_sharded_round.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.core.aircomp import ChannelConfig, sample_channel_gains
from repro.core.compress import randmask_indices
from repro.core.scheduler import (TAG_CHANNEL, TAG_COMPRESS, TAG_NOISE,
                                  TAG_QUANT, TAG_SCHED, SchedulerConfig,
                                  blackout_active, counter_latencies,
                                  fault_channel_mask, fault_payload_masks,
                                  inject_payload_faults, round_tag_key,
                                  scenario_latencies, scenario_masks)
from repro.fl.fused import FusedPAOTA
from repro.fl.runtime import (GroupTopology, RoundCarry, RoundStreams,
                              init_round_carry, scan_rounds, scan_windows)
from repro.fl.server import PAOTAConfig
from repro.launch.mesh import data_axes
from repro.sharding.rules import batch_specs, stack_client_specs

OUT_KEYS = ("n_participants", "time", "mean_staleness", "beta_mean",
            "varsigma", "p2_objective", "n_screened", "rolled_back")


def vary_over(tree, axes):
    """Type every leaf of ``tree`` as varying over the mesh ``axes``.

    Local SGD differentiates the broadcast globals, which ``shard_map``
    types as invariant over the client axes. Differentiating an invariant
    input against per-shard data makes ``jax.grad`` psum the cotangent
    over those axes (the transpose of the implicit invariant-to-varying
    cast), which would mix every shard's clients into each client's
    gradient. Casting the globals to varying BEFORE the gradient keeps
    each client's local step its own, with no collective."""
    def leaf(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x
    return jax.tree_util.tree_map(leaf, tree)


class ShardedPAOTA(FusedPAOTA):
    """Drop-in ``FusedPAOTA`` whose scan runs sharded over the mesh client
    axis.

    ``mesh`` defaults to all local devices as one client axis
    (``repro.launch.mesh.make_client_mesh``); ``client_axes`` defaults to
    the mesh's ("pod",)/"data" axes (``data_axes``) — pass
    ``client_axes_for(model_cfg, mesh)`` to follow an architecture's
    placement policy. A client-axis extent that does not divide K pads
    the federation with masked phantom clients (never ready, zero power)
    rather than refusing.

    ``params_mode="pytree"`` + ``model_cfg``: carry the params pytree
    natively with each stacked leaf placed by ``stack_client_specs(...,
    model_cfg, mesh, client_axes)`` (``model_cfg=None`` places leading
    client axes only — the right policy for structureless pytrees like
    the MLP).

    ``group_period=N`` (N >= 1) enables grouped aggregation: the client
    axes in ``pod_axes`` (default: the first client axis) index the pods;
    non-sync periods psum intra-pod only and the cross-pod model-sized
    psum fires once per N-period window. ``advance`` then moves in whole
    windows (``n_rounds`` must be a multiple of N). N=1 is the flat
    program bit-for-bit.

    ``tp_axes`` (pytree mode): mesh axes the model storage TP-shards over
    inside each client shard (default: the mesh's "tp" axis when present).
    Extent 1 is the flat program bit-for-bit; extent > 1 slices one
    trailing dim of each stacked payload leaf (placement from
    ``stack_client_specs``; leaves with no dividing dim stay
    TP-replicated) — see the module docstring.
    """

    def __init__(self, init_params, clients, chan: ChannelConfig,
                 sched_cfg: SchedulerConfig, cfg: PAOTAConfig, *,
                 mesh=None, client_axes=None, params_mode: str = "raveled",
                 model_cfg=None, pending_dtype: str = "float32",
                 donate: bool = True, group_period: int = 0, pod_axes=None,
                 cohort_size: int | None = None, scenario=None,
                 compress: str | None = None, compress_ratio: float = 1.0,
                 slot_dtype: str | None = None,
                 error_feedback: bool = True, tp_axes=None, faults=None,
                 screen: bool = False, screen_max_norm: float = 0.0,
                 divergence_factor: float = 0.0, checkpoint_every: int = 0,
                 checkpoint_dir: str | None = None):
        if mesh is None:
            from repro.launch.mesh import make_client_mesh
            mesh = make_client_mesh()
        self.mesh = mesh
        axes = tuple(client_axes) if client_axes else data_axes(mesh)
        if not axes:
            raise ValueError(f"mesh {mesh.axis_names} has no client axis")
        self.client_axes = axes
        self.n_shards = int(math.prod(mesh.shape[a] for a in axes))
        # intra-client TP: default to the mesh's dedicated "tp" axis;
        # extent 1 (or no such axis) keeps the historical flat program
        if tp_axes is None:
            tp_ax = tuple(a for a in mesh.axis_names
                          if a == "tp" and a not in axes)
        else:
            tp_ax = tuple(tp_axes)
            bad = [a for a in tp_ax
                   if a not in mesh.axis_names or a in axes]
            if bad:
                raise ValueError(
                    f"tp_axes={tp_ax}: {bad} must be non-client mesh axes "
                    f"(mesh axes {mesh.axis_names}, client_axes={axes})")
        self.tp_axes = tp_ax
        self.tp_shards = int(math.prod(mesh.shape[a] for a in tp_ax)) \
            if tp_ax else 1
        self._tp = None
        if self.tp_shards > 1:
            if len(self.tp_axes) > 1:
                raise NotImplementedError(
                    f"tp_axes={self.tp_axes}: intra-client TP supports a "
                    f"single mesh axis (leaf dims shard over one axis); "
                    f"the nearest supported configuration merges them into "
                    f"one 'tp' axis of extent {self.tp_shards}")
            if compress:
                raise NotImplementedError(
                    f"compress='{compress}' does not compose with "
                    f"intra-client TP (tp axes {self.tp_axes}, extent "
                    f"{self.tp_shards}) yet — the (m, s) compressed slot "
                    f"planes are raveled coordinate sets with no per-leaf "
                    f"TP split; the nearest supported configurations are "
                    f"compress='{compress}' on a client-axes-only mesh, or "
                    f"TP with compress=None")
            if cohort_size:
                raise NotImplementedError(
                    f"cohort_size={cohort_size} does not compose with "
                    f"intra-client TP (tp axes {self.tp_axes}, extent "
                    f"{self.tp_shards}) yet — the cohort payload plane is "
                    f"raveled (m, d) slots; the nearest supported "
                    f"configurations are cohort_size={cohort_size} on a "
                    f"client-axes-only mesh, or TP with cohort_size=None "
                    f"(dense payload planes)")
            if group_period:
                raise NotImplementedError(
                    f"group_period={group_period} does not compose with "
                    f"intra-client TP (tp axes {self.tp_axes}, extent "
                    f"{self.tp_shards}) yet — the held intra-pod partial "
                    f"is a flat model-sized accumulator with no TP split; "
                    f"the nearest supported configurations are "
                    f"group_period={group_period} with TP extent 1, or TP "
                    f"with group_period=0 (flat sync every period)")
            if params_mode != "pytree":
                raise NotImplementedError(
                    f"params_mode='raveled' does not compose with "
                    f"intra-client TP (tp axes {self.tp_axes}, extent "
                    f"{self.tp_shards}) — the flat (K, d) stack has no "
                    f"leaf dims to TP-shard; the nearest supported "
                    f"configurations are params_mode='pytree' (per-leaf TP "
                    f"placement), or raveled on a client-axes-only mesh")
        if params_mode == "pytree":
            other = {a: mesh.shape[a] for a in mesh.axis_names
                     if a not in axes and a not in self.tp_axes
                     and mesh.shape[a] > 1}
            if other:
                named = ", ".join(f"'{a}' (extent {mesh.shape[a]})"
                                  for a in sorted(other))
                raise NotImplementedError(
                    f"params_mode='pytree' shards the client axes and the "
                    f"tp_axes only, but non-client mesh axis {named} has "
                    f"extent > 1: it would split the stacked leaves' model "
                    f"dims outside the round's TP-aware reductions. Either "
                    f"name it in tp_axes (intra-client TP — the model "
                    f"storage shards over it), use params_mode='raveled' "
                    f"(the flat (K, d) federation over the client axes), "
                    f"rebuild the mesh with extent 1 on {sorted(other)}, "
                    f"or include the axis in client_axes.")
        # grouped-aggregation topology: pod axes index the groups, the
        # remaining client axes are intra-pod
        if group_period < 0:
            raise ValueError(f"group_period={group_period} (expected >= 0)")
        if pod_axes is not None and not group_period:
            raise ValueError("pod_axes without group_period: pass "
                             "group_period=N >= 1 to enable grouped "
                             "aggregation")
        self._grouping = None
        self.n_pod_groups = 1
        if group_period:
            pods = tuple(pod_axes) if pod_axes else (axes[0],)
            bad = [a for a in pods if a not in axes]
            if bad or len(set(pods)) != len(pods):
                raise ValueError(f"pod_axes={pods} must be distinct client "
                                 f"axes (client_axes={axes})")
            intra = tuple(a for a in axes if a not in pods)
            self._grouping = GroupTopology(
                pod_axes=pods, intra_axes=intra,
                intra_shards=int(math.prod(mesh.shape[a] for a in intra)))
            self.n_pod_groups = int(math.prod(mesh.shape[a] for a in pods))
        if cohort_size and group_period:
            raise NotImplementedError(
                "active-cohort mode does not compose with grouped "
                "aggregation yet: the held-window partials are dense-plane "
                "accumulators (pass cohort_size=None or group_period=0)")
        if faults is not None and getattr(faults, "has_blackout", False):
            pods = (tuple(pod_axes) if pod_axes else (axes[0],)) \
                if group_period else ()
            if pods and pods != axes[:len(pods)]:
                raise NotImplementedError(
                    f"pod_blackout with pod_axes={pods}: the blackout's "
                    f"pod -> client-row map assumes the pod axes LEAD the "
                    f"client axes {axes} (pods own contiguous row blocks); "
                    f"the nearest supported configuration reorders "
                    f"client_axes to put {pods} first")
        # super() builds the engine, RoundCfg, keys, and jits _run_scan —
        # which the overrides below turn into the shard_map program
        super().__init__(init_params, clients, chan, sched_cfg, cfg,
                         params_mode=params_mode, pending_dtype=pending_dtype,
                         donate=donate, cohort_size=cohort_size,
                         scenario=scenario, compress=compress,
                         compress_ratio=compress_ratio,
                         slot_dtype=slot_dtype,
                         error_feedback=error_feedback, faults=faults,
                         screen=screen, screen_max_norm=screen_max_norm,
                         divergence_factor=divergence_factor,
                         checkpoint_every=checkpoint_every,
                         checkpoint_dir=checkpoint_dir)
        if group_period:
            self._rcfg = self._rcfg._replace(group_period=group_period)
            if self.checkpoint_every % group_period:
                raise ValueError(
                    f"checkpoint_every={self.checkpoint_every} must be a "
                    f"multiple of group_period={group_period}: the grouped "
                    f"scan advances whole windows, so snapshots land on "
                    f"window boundaries only")
        # phantom-client padding: pad K to the next multiple of the
        # client-axis extent with masked never-ready clients
        self.k_pad = -(-self.k // self.n_shards) * self.n_shards
        self.n_phantom = self.k_pad - self.k
        self.k_local = self.k_pad // self.n_shards
        if self.n_phantom:
            ph = self.n_phantom
            eng = self.engine
            pad0 = lambda a: jnp.concatenate(
                [jnp.asarray(a),
                 jnp.zeros((ph,) + a.shape[1:], a.dtype)])
            eng._x, eng._y = pad0(eng._x), pad0(eng._y)
            # phantom "datasets" are one zero row: minibatch plans draw
            # index 0 only, the trained output rows are never consumed
            # (ready stays False so pending never takes them)
            eng._n_dev = jnp.concatenate(
                [eng._n_dev, jnp.ones((ph,), eng._n_dev.dtype)])
            # heterogeneity traits pad with the identity hyperparameters
            # (phantom rows are never consumed, but the gathers by global
            # id must stay in bounds)
            pad1 = lambda a: jnp.concatenate(
                [a, jnp.ones((ph,), a.dtype)])
            if eng._steps_k is not None:
                eng._steps_k = pad1(eng._steps_k)
            if eng._batch_k is not None:
                eng._batch_k = pad1(eng._batch_k)
        # the cohort splits into shard-LOCAL slot sets (slot gathers and
        # the refill top_k never cross shards): m must tile the shards, and
        # each shard's slots cannot exceed its client rows. Slot refill is
        # per shard — a policy difference vs the fused driver's global
        # priority order (documented; at m = K both pin every client to a
        # permanent slot and match the dense path).
        self.m_local = 0
        if self.cohort_size:
            if self.cohort_size % self.n_shards:
                lo = (self.cohort_size // self.n_shards) * self.n_shards
                hi = lo + self.n_shards
                near = (f"{hi}" if lo == 0
                        else f"{lo} and {hi}")
                raise ValueError(
                    f"cohort_size={self.cohort_size} must be divisible by "
                    f"the {self.n_shards} client shards (slots are "
                    f"shard-local); the nearest valid cohort sizes are "
                    f"{near}")
            self.m_local = self.cohort_size // self.n_shards
            if self.m_local > self.k_local:
                raise ValueError(
                    f"cohort_size={self.cohort_size} gives {self.m_local} "
                    f"slots per shard but each shard holds only "
                    f"{self.k_local} client rows")
        ax = axes if len(axes) != 1 else axes[0]
        self._ax = ax
        if params_mode == "pytree":
            tp_on = self.tp_shards > 1
            stacked_struct = jax.tree_util.tree_map(
                lambda g: jax.ShapeDtypeStruct((self.k_pad,) + g.shape,
                                               g.dtype), self._init_global)
            pend_spec = stack_client_specs(
                stacked_struct, model_cfg, mesh, axes,
                tp_axis=(self.tp_axes[0] if tp_on else None))
            # every kept-out axis is extent 1 (guard above), so dropping
            # its trailing assignments changes nothing physically — but it
            # lets shard_map's replication checker see that the psum over
            # the client (x TP) axes fully replicates the globals. With TP
            # active the TP assignments are KEPT: they are the payload
            # placement.
            keep = axes + (self.tp_axes if tp_on else ())
            pend_spec = jax.tree_util.tree_map(
                lambda s: self._client_axes_only(s, keep), pend_spec)
            if tp_on:
                # leaf_dims come FROM the computed pend_spec, so GSPMD
                # placement and the runtime's slicing can never disagree
                self._tp = self._derive_tp(pend_spec)
            glob_spec = jax.tree_util.tree_map(lambda _: P(),
                                               self._init_global)
        else:
            pend_spec, glob_spec = P(ax, None), P()
        if self._grouping is not None:
            pods = self._grouping.pod_axes
            # held rows shard over the pod axes and replicate intra-pod
            # (the intra-pod psum that builds them replicates them there)
            held_spec = P(pods[0] if len(pods) == 1 else pods, None)
        else:
            held_spec = None
        slot_spec = P(ax) if self.cohort_size else None
        # compressed cohort planes: the (m, s) slot planes and the (K, s)
        # parked-residual planes all shard their leading (client) axis,
        # like the payload plane they replace
        comp_spec = P(ax, None) if self._rcfg.compress else None
        ef_spec = comp_spec if self._rcfg.error_feedback else None
        # the divergence detector's last-good slot replicates like the
        # globals it snapshots (None subtree when the detector is off)
        diverg = self._rcfg.divergence_factor > 0.0
        self._carry_specs = RoundCarry(
            t=P(), time=P(), ready=P(ax), busy_lat=P(ax),
            model_round=P(ax), global_vec=glob_spec, prev_global=glob_spec,
            # transmit='delta' carries no pending plane (None subtree)
            pending=None if self._rcfg.transmit_delta else pend_spec,
            # cohort mode: the payload planes' leading axis is the m slots
            # (m_local per shard) — same specs, smaller extent
            deltas=pend_spec, held=held_spec,
            slot_client=slot_spec, slot_live=slot_spec,
            slot_idx=comp_spec,
            slot_scale=(P(ax) if self._rcfg.slot_dtype == "int8" else None),
            slot_resid=ef_spec, slot_resid_idx=ef_spec,
            resid_val=ef_spec, resid_idx=ef_spec,
            good_global=glob_spec if diverg else None,
            good_norm2=P() if diverg else None)
        data_sp = batch_specs({"x": self.engine._x, "y": self.engine._y},
                              (), (axes,))
        self._x_spec, self._y_spec = data_sp["x"], data_sp["y"]
        self._out_specs = {k: P() for k in OUT_KEYS}
        # place the padded federation over the client axis ONCE — advance()
        # then never pays a reshard (the scan's in_specs match)
        self.engine._x = jax.device_put(
            self.engine._x, NamedSharding(mesh, self._x_spec))
        self.engine._y = jax.device_put(
            self.engine._y, NamedSharding(mesh, self._y_spec))

    @staticmethod
    def _client_axes_only(spec, axes):
        """Strip mesh axes outside ``axes`` from a PartitionSpec (all such
        axes are extent 1 in pytree mode — see the constructor guard;
        with TP active the TP axes are part of ``axes`` and survive)."""
        def keep(entry):
            if entry is None:
                return None
            if isinstance(entry, tuple):
                kept = tuple(a for a in entry if a in axes)
                return kept if kept else None
            return entry if entry in axes else None
        return P(*(keep(e) for e in spec))

    def _derive_tp(self, pend_spec):
        """Static ``TPTopology`` read off the computed pend_spec tree: for
        each stacked leaf, the (unstacked) trailing-dim index its spec
        assigns to the TP axes, -1 when none (TP-replicated leaf)."""
        from repro.sharding.tp import TPTopology
        tp_set = set(self.tp_axes)
        dims = []
        for sp in jax.tree_util.tree_leaves(
                pend_spec, is_leaf=lambda s: isinstance(s, P)):
            dim = -1
            for i, entry in enumerate(sp):
                names = (entry if isinstance(entry, tuple)
                         else (entry,) if entry else ())
                if not any(a in tp_set for a in names):
                    continue
                if i == 0 or (set(names) - tp_set) or dim >= 0:
                    raise NotImplementedError(
                        f"unsupported TP placement {sp}: the TP axes "
                        f"{self.tp_axes} must occupy exactly one trailing "
                        f"leaf dim, alone")
                dim = i - 1
            dims.append(dim)
        return TPTopology(
            axes=self.tp_axes,
            extents=tuple(self.mesh.shape[a] for a in self.tp_axes),
            shards=self.tp_shards, leaf_dims=tuple(dims))

    # ------------------------------------------------------------------
    # phantom-aware full-federation streams (round-0 init runs these on
    # the placed data before the scan takes over): real clients see the
    # exact unpadded draws, phantoms get busy_lat = +inf so sched_advance
    # can never flip their ready bit
    # ------------------------------------------------------------------
    def _streams(self) -> RoundStreams:
        base = super()._streams()
        if not self.n_phantom:
            return base

        def pad_fill(v, fill):
            return jnp.concatenate(
                [v, jnp.full((self.n_phantom,), fill, v.dtype)])

        scen = None
        if base.scenario is not None:
            def scen(t):
                avail, drop = base.scenario(t)
                return pad_fill(avail, False), pad_fill(drop, False)
        prio = None
        if base.sched_priority is not None:
            # -inf score = never schedulable: phantoms can win a slot in no
            # round (the refill gate is score > -inf)
            prio = lambda r: pad_fill(base.sched_priority(r), -jnp.inf)
        return RoundStreams(
            local_train=base.local_train,   # engine arrays already padded
            latencies=lambda r: pad_fill(base.latencies(r), jnp.inf),
            channel=lambda t: pad_fill(base.channel(t), 0.0),
            noise_key=base.noise_key,
            scenario=scen,
            cohort_train=base.cohort_train,  # gathers by id: already padded
            sched_priority=prio,
            compress_mask=base.compress_mask,   # slot planes are never
            quant_key=base.quant_key,           # client-indexed: no padding
        )

    # ------------------------------------------------------------------
    # shard-local streams: identical global draws, this shard's rows
    # ------------------------------------------------------------------
    def _shard_offset(self):
        """First global client id on this shard (traced, inside shard_map):
        row-major flattening of the client-axis coordinates."""
        idx = jnp.int32(0)
        for a in self.client_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx * self.k_local

    def _shard_streams(self, offset) -> RoundStreams:
        k, k_loc, ph = self.k, self.k_local, self.n_phantom
        sc, chan = self.sched_cfg, self.chan
        n_dev = self.engine._n_dev          # (K_pad,) consts: replicated

        def slice_k(full):
            return jax.lax.dynamic_slice(full, (offset,), (k_loc,))

        def pad_slice(full, fill):
            """Slice this shard's rows out of a full-K draw vector, padded
            to K_pad with the phantom fill first — a shard that straddles
            the real/phantom boundary must not clamp into real rows."""
            if ph:
                full = jnp.concatenate(
                    [full, jnp.full((ph,), fill, full.dtype)])
            return slice_k(full)

        def local_train(global_state, x, y, r):
            global_state = vary_over(global_state, self.client_axes)
            cids = (offset.astype(jnp.uint32)
                    + jnp.arange(k_loc, dtype=jnp.uint32))
            idx = self.engine.round_plan(r, client_ids=cids,
                                         n_samples=slice_k(n_dev))
            steps = self.engine.steps_for(cids)
            if self.params_mode == "pytree":
                return self.engine._train_all_tree(global_state, x, y, idx,
                                                   steps)
            return self.engine._train_all(self.unravel(global_state), x, y,
                                          idx, steps)

        def cohort_train(global_state, x, y, r, ids):
            # slot ids are shard-LOCAL rows of (x, y); every draw keys on
            # the GLOBAL client id, so a client's trained row is identical
            # whichever shard/slot computes it
            global_state = vary_over(global_state, self.client_axes)
            gids = (offset.astype(jnp.uint32) + ids.astype(jnp.uint32))
            return self._train_slots(global_state, x, y, r, ids, gids)

        scn = self.scenario
        if scn is None:
            lat = lambda r: pad_slice(counter_latencies(
                self._lat_key, r, k, sc.lat_lo, sc.lat_hi), jnp.inf)
        else:
            lat = lambda r: pad_slice(scenario_latencies(
                self._lat_key, r, k, sc.lat_lo, sc.lat_hi, scn), jnp.inf)
        scen_cb = None
        if scn is not None and scn.has_masks:
            def scen_cb(t):
                avail, drop = scenario_masks(self._lat_key, t, k, scn)
                return pad_slice(avail, False), pad_slice(drop, False)
        prio = None
        if self.cohort_size:
            # the SAME full-K priority draw the fused driver makes, this
            # shard's rows, phantoms pinned -inf (never schedulable); the
            # refill top_k itself is shard-local — a documented policy
            # difference vs the fused driver's global priority order
            prio = lambda r: pad_slice(jax.random.uniform(
                round_tag_key(self._lat_key, r, TAG_SCHED), (k,)), -jnp.inf)
        compress_mask = quant_key = None
        if self.compress == "randmask" and self.compress_s < self.d:
            # the SAME replicated mask the fused driver draws: every shard
            # re-derives it from the counter stream, no collective needed
            compress_mask = lambda r: randmask_indices(
                round_tag_key(self._srv_key, r, TAG_COMPRESS), self.d,
                self.compress_s)
        if self._rcfg.slot_dtype == "int8":
            # fold the shard offset into the dither key so shard-local
            # draws are independent across shards (same shape, own stream)
            quant_key = lambda r: jax.random.fold_in(
                round_tag_key(self._srv_key, r, TAG_QUANT), offset)
        channel = lambda t: pad_slice(sample_channel_gains(
            round_tag_key(self._srv_key, t, TAG_CHANNEL), k, chan), 0.0)

        # fault streams: the SAME full-K draws the fused driver makes,
        # sliced to this shard's rows (phantoms never fault)
        fc = self.faults
        if fc is not None and fc.has_payload_faults:
            base_local, base_cohort = local_train, cohort_train

            def local_train(global_state, x, y, r):          # noqa: F811
                trained = base_local(global_state, x, y, r)
                nm, bm = fault_payload_masks(self._lat_key, r, k, fc)
                return inject_payload_faults(
                    trained, global_state, pad_slice(nm, False),
                    pad_slice(bm, False), fc)

            def cohort_train(global_state, x, y, r, ids):    # noqa: F811
                trained = base_cohort(global_state, x, y, r, ids)
                nm, bm = fault_payload_masks(self._lat_key, r, k, fc)
                if ph:
                    # slot gids reach into the phantom pad: extend the
                    # masks with never-faulting rows before the gather
                    pad = jnp.zeros((ph,), bool)
                    nm = jnp.concatenate([nm, pad])
                    bm = jnp.concatenate([bm, pad])
                gids = offset.astype(jnp.uint32) + ids.astype(jnp.uint32)
                return inject_payload_faults(trained, global_state,
                                             nm[gids], bm[gids], fc)
        if fc is not None and fc.has_channel_faults:
            base_chan = channel

            def channel(t):                                  # noqa: F811
                h = base_chan(t)
                fade = pad_slice(fault_channel_mask(self._lat_key, t, k, fc),
                                 False)
                return jnp.where(fade, h * jnp.float32(fc.deep_fade_gain), h)
        if fc is not None and fc.has_blackout:
            # pod blackout composes into the scenario availability mask:
            # the pod axes lead the client axes (constructor guard), so
            # pod p owns the contiguous rows [p, p+1) * k_pad / n_pods
            rows_per_pod = self.k_pad // self.n_pod_groups
            blk_full = jnp.asarray(np.isin(
                np.arange(self.k_pad) // rows_per_pod,
                [int(p) for p in fc.pod_blackout]))
            base_scen = scen_cb

            def scen_cb(t):                                  # noqa: F811
                blk = blackout_active(fc, t) & jax.lax.dynamic_slice(
                    blk_full, (offset,), (k_loc,))
                if base_scen is None:
                    return ~blk, jnp.zeros_like(blk)
                avail, drop = base_scen(t)
                return avail & ~blk, drop

        return RoundStreams(
            local_train=local_train,
            latencies=lat,
            channel=channel,
            noise_key=lambda t: round_tag_key(self._srv_key, t, TAG_NOISE),
            scenario=scen_cb,
            cohort_train=cohort_train if self.cohort_size else None,
            sched_priority=prio,
            compress_mask=compress_mask,
            quant_key=quant_key,
        )

    # ------------------------------------------------------------------
    # the sharded scan (replaces FusedPAOTA's single-device _run_scan;
    # per-client init math has no cross-client reduction, so GSPMD runs
    # _init_carry row-parallel over the same placed data — the grouped
    # override below only adds the zeroed held slot)
    # ------------------------------------------------------------------
    def _init_carry(self, vec, x, y) -> RoundCarry:
        if self.cohort_size:
            # cohort init gathers data/payload rows by shard-LOCAL slot ids,
            # so it must run INSIDE shard_map (under plain GSPMD jit those
            # gathers would read global rows). Each shard seeds its first
            # m_local slots from its own real clients; a shard whose rows
            # are all phantom padding starts with every slot dead.
            glob_spec = self._carry_specs.global_vec

            def body(v, xs, ys):
                offset = self._shard_offset()
                n_real = jnp.clip(jnp.int32(self.k) - offset, 0,
                                  self.k_local)
                return init_round_carry(
                    v, xs, ys, streams=self._shard_streams(offset),
                    pending_dtype=self._rcfg.pending_dtype,
                    keep_pending=not self._rcfg.transmit_delta,
                    rcfg=self._rcfg, m=self.m_local, n_real=n_real)

            smap = jax.shard_map(body, mesh=self.mesh,
                                 in_specs=(glob_spec, self._x_spec,
                                           self._y_spec),
                                 out_specs=self._carry_specs)
            return smap(vec, x, y)
        carry = super()._init_carry(vec, x, y)
        if self._grouping is not None:
            carry = carry._replace(held=jnp.zeros(
                (self.n_pod_groups, self.d + 1), jnp.float32))
        return carry

    def _run_scan(self, carry: RoundCarry, x, y, n_rounds: int):
        axes = self.client_axes
        grouping, n = self._grouping, self._rcfg.group_period

        def body(c, xs, ys):
            streams = self._shard_streams(self._shard_offset())
            if grouping is None:
                return scan_rounds(c, xs, ys, n_rounds, rcfg=self._rcfg,
                                   streams=streams, axis_name=axes,
                                   tp=self._tp)
            return scan_windows(c, xs, ys, n_rounds // n, rcfg=self._rcfg,
                                streams=streams, axis_name=axes,
                                grouping=grouping)

        if grouping is not None and n_rounds % n:
            raise ValueError(
                f"grouped aggregation advances whole windows: n_rounds="
                f"{n_rounds} is not a multiple of group_period={n}")
        smap = jax.shard_map(body, mesh=self.mesh,
                             in_specs=(self._carry_specs, self._x_spec,
                                       self._y_spec),
                             out_specs=(self._carry_specs, self._out_specs))
        carry, outs = smap(carry, x, y)
        if grouping is not None:
            # window-stacked (n_windows, N) metrics back to the flat
            # (n_rounds,) timeline the driver's history expects
            outs = {k: v.reshape((n_rounds,)) for k, v in outs.items()}
        return carry, outs
