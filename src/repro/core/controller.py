"""The Federation Controller — model state, transport and store plumbing.

Implements the controller lifecycle of paper Figs. 1/9/10 with the
re-engineered operations of §3, but — since the event-driven round engine
landed (``core/engine.py``) — it no longer *runs* protocols itself: the
engine's single arrival-driven loop consults the protocol policy
(``core/scheduler.ProtocolPolicy``) and calls back into the controller's
plumbing surface:

* **serialize-once broadcast dispatch** — the global model is serialized at
  most **once per model version** (:meth:`Controller._broadcast`,
  ``Channel.broadcast`` straight off the flat ``global_buffer``), so
  per-round dispatch cost is O(P + N), independent of federation size at
  fixed payload.
* **measured upload ingest** (:meth:`Controller.ingest`) — learners hold the
  manifest and the channel handle (shipped once at registration) and send
  packed ``(P,)`` buffers through the measured uplink; arrival is a codec
  decode plus a straight donated arena row write, and the EWMA learner
  profile (``core/scheduler.LearnerProfile``) absorbs the task's measured
  seconds-per-step and wire bytes.
* **aggregation plumbing** — :meth:`Controller.aggregate_round` (cohort
  FedAvg / secure sum) and :meth:`Controller.aggregate_community`
  (staleness-damped async update, in the clear or through a per-epoch
  :class:`~repro.core.secure.MaskSession`), both committing through the
  server optimizer and bumping the model version.
* **wire-cost model** (:meth:`Controller.wire_time_s`) — the per-learner
  round-trip virtual wire estimate (downlink broadcast + uplink payload)
  the semi-sync policy subtracts from its hyper-period budget.
* **device-resident arena** (``store_mode="arena"``, the default) — uploads
  are donated in-place row writes into a persistent ``(n_max, P)`` device
  buffer (``core/store.ArenaStore``), optionally column-sharded over a mesh
  (``arena_mesh=``); ``store_mode="stack"`` keeps the legacy hash-map +
  re-stack path for parity.

Workflow execution — cohort selection, dispatch, arrival handling,
aggregation timing, evaluation fan-out — lives in ``engine.run``; see
``docs/ENGINE.md``.
"""

from __future__ import annotations

import math
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import aggregation, packing, transport
from repro.core.engine import RoundEngine, RoundTimings, UploadRejectedError
from repro.core.journal import EventJournal, jsonable
from repro.core.learner import Learner, LocalUpdate
from repro.core.metrics import Telemetry
from repro.core.scheduler import LearnerProfile, ProtocolPolicy, SyncProtocol
from repro.core.selection import SelectionPolicy
from repro.core.server_opt import ServerOptimizer, make_server_optimizer
from repro.core.store import ArenaStore, ModelRecord, ModelStore
from repro.core.transport import Broadcast, Channel, get_upload_codec

__all__ = ["RoundTimings", "Controller"]


AggregateFn = Callable[[jax.Array, jax.Array], jax.Array]


class Controller:
    """The federation controller: model state + transport + store plumbing.

    Protocol execution is delegated to :attr:`engine`
    (``core/engine.RoundEngine``): ``controller.engine.run(rounds=N)`` for
    the round-based policies, ``engine.run(total_updates=N)`` for the
    continuous (async) one.

    Parameters
    ----------
    protocol:
        A :class:`~repro.core.scheduler.ProtocolPolicy`
        (Sync/SemiSync/Async protocol object).
    aggregate_fn:
        ``(stack (N,P), weights (N,)) -> (P,)``.  Defaults to the fused
        FedAvg; swap in the Pallas kernel op or a robust rule.
    store_mode:
        ``"arena"`` (default) aggregates straight off the device-resident
        :class:`ArenaStore`; ``"stack"`` is the legacy re-stack path.
    masked_aggregate_fn:
        ``(arena (N_max,P), weights (N_max,), mask (N_max,)) -> (P,)`` — the
        arena-path rule.  Defaults to the fused masked FedAvg (or, if a
        custom ``aggregate_fn`` was given, to ``aggregate_fn`` with the mask
        folded into the weights — correct for the weighted-average family,
        not for order statistics like the median; pass an explicit masked
        rule for those).
    secure:
        If True, uploads are mask-encoded and the controller only sums
        (``core/secure``) — it never sees an individual model.  Composes
        with every policy, including the continuous (async) one: each
        community update opens a fresh per-epoch mask session keyed by the
        global model version (``core/secure.MaskSession``).
    arena_mesh:
        Optional :class:`jax.sharding.Mesh`.  When given (arena mode only),
        the persistent ``(n_max, P)`` arena is **column-sharded** over the
        mesh's data axis (``launch/mesh.make_controller_mesh`` builds a 1-D
        one over all local devices): uploads scatter once and write
        shard-locally, and every aggregation policy — plain, staleness-
        weighted async, secure sum — reduces per shard with zero collectives.
        Numerics are identical to the single-device arena
        (``tests/test_arena_sharded.py``); see ``docs/ARENA.md``.
    arena_axes:
        Mesh axis name(s) to split ``P`` over (default: the ``"data"`` axis
        if the mesh has one, else every axis).
    flat_uploads:
        If True (default), every registered learner receives the model
        manifest (plus the arena row width and the channel handle) once at
        registration and sends its uploads through the measured uplink
        (``Channel.upload``) as codec-encoded wire envelopes, so
        :meth:`ingest` never flattens a pytree (``upload_fallback_packs``
        counts the times it had to).  False keeps the legacy
        pack-on-arrival path, for parity testing — those uploads still
        cross the measured uplink (the controller stands in for the
        learner's send half), so ``ChannelStats`` reconciles on every path.
    upload_codec:
        Uplink wire format: ``"raw"`` (default, bit-transparent f32 bytes)
        or ``"int8"`` (blockwise quantization, ~3.9x fewer uplink bytes), or
        a codec object (``core/transport.get_upload_codec``).  ``None``
        (default) keeps whatever the channel already uses; when set, it is
        installed on the controller's channel — including an explicitly
        passed ``channel=``, whose previous upload codec it replaces.
    profile_decay:
        EWMA decay for the per-learner seconds-per-step estimate
        (``core/scheduler.LearnerProfile``); 0 reproduces the legacy
        last-sample behaviour.
    aggregation_rule / trim_k:
        The community-model reduction: ``"fedavg"`` (default),
        ``"median"`` (coordinate-wise median) or ``"trimmed_mean"`` (drop
        the ``trim_k`` extremes per coordinate per side).  The robust
        rules run as masked reductions straight off the arena (sharded
        variants when ``arena_mesh`` is set), are weight-blind order
        statistics, exclude custom aggregate functions and ``secure``,
        and are rejected by the staleness-weighted protocols — see the
        support matrix in ``docs/PROTOCOLS.md``.
    admission_control / admission_clip_factor / admission_ewma_decay /
    admission_warmup:
        The upload admission screen (:meth:`_screen_upload`): non-finite
        buffers are rejected before they can touch the store, and — once
        ``admission_warmup`` accepted uploads have seeded an EWMA of
        update norms — outlier norms beyond ``admission_clip_factor``
        times the EWMA are clipped down to the limit.  On by default;
        forced off under ``secure`` (mask-encoded rows have meaningless
        norms).  Counters: ``engine.uploads.rejected.nonfinite``,
        ``engine.uploads.clipped``.
    quarantine_threshold / quarantine_decay:
        Repeat admission offenders are quarantined: each rejected or
        clipped upload adds 1 to a per-learner score that decays by
        ``quarantine_decay`` per round, and learners at or over
        ``quarantine_threshold`` are skipped by cohort selection until
        decay releases them (fail-open when everyone is quarantined).
        The defaults (threshold 2.0, decay 0.75) quarantine on the third
        consecutive offending round (scores 1.0, 1.75, 2.31...) and never
        on a single glitch.  Composes with ``ReputationProtocol`` — offenses
        also feed the reputation EWMA through
        ``LearnerProfile.observe_contribution``.
    journal / journal_sink / journal_capacity:
        The engine's flight recorder (``core/journal.EventJournal``).  Pass
        a pre-built journal (tests inject a deterministic clock) or let the
        controller build one: ``journal_sink`` optionally persists records
        as JSONL (path or file object; written off the engine loop thread by
        a background flusher) and ``journal_capacity`` bounds the in-memory
        ring (0 disables recording).
    checkpoint_every / checkpoint_dir:
        Crash-consistency: every ``checkpoint_every`` completed rounds the
        engine calls :meth:`save_checkpoint` into ``checkpoint_dir`` —
        global model + version + learner profiles + store state + journal
        cursor.  :meth:`restore` on a freshly constructed controller (same
        config, learners registered) resumes mid-workflow bit-identically.
        Both default to off; ``engine.run(checkpoint_every=..., ...)``
        overrides per run.

    All wire/store/dispatch counters live behind one
    :class:`~repro.core.metrics.Telemetry` registry at
    :attr:`Controller.telemetry` (``telemetry.value(name)`` /
    ``telemetry.snapshot()``); the legacy attributes
    (``dispatch_serializations``, ``upload_fallback_packs``,
    ``channel.stats.*``, ``arena.bytes_ingested``...) remain as deprecated
    read shims.  Names: ``docs/OBSERVABILITY.md``.
    """

    def __init__(
        self,
        protocol: ProtocolPolicy | None = None,
        selection: SelectionPolicy | None = None,
        aggregate_fn: AggregateFn | None = None,
        server_optimizer: ServerOptimizer | None = None,
        store: ModelStore | None = None,
        channel: Channel | None = None,
        secure: bool = False,
        max_dispatch_workers: int = 32,
        secure_seed: int = 0,
        store_mode: str = "arena",
        masked_aggregate_fn: Callable | None = None,
        arena_n_max: int = 8,
        arena_row_align: int = 1024,
        arena_mesh: Any = None,
        arena_axes: Any = None,
        arena_dtype: str = "f32",
        sparse_mode: str = "densify",
        flat_uploads: bool = True,
        upload_codec: Any = None,
        profile_decay: float = 0.5,
        journal: EventJournal | None = None,
        journal_sink: Any = None,
        journal_capacity: int = 4096,
        checkpoint_every: int | None = None,
        checkpoint_dir: str | None = None,
        aggregation_rule: str = "fedavg",
        trim_k: int = 1,
        admission_control: bool = True,
        admission_clip_factor: float = 4.0,
        admission_ewma_decay: float = 0.9,
        admission_warmup: int = 8,
        quarantine_threshold: float = 2.0,
        quarantine_decay: float = 0.75,
    ):
        if store_mode not in ("arena", "stack"):
            raise ValueError(f"store_mode must be 'arena' or 'stack', got {store_mode!r}")
        if arena_dtype not in ("f32", "int8"):
            raise ValueError(
                f"arena_dtype must be 'f32' or 'int8', got {arena_dtype!r}"
            )
        if arena_dtype == "int8":
            # The quantized-resident arena supports exactly the weighted-
            # average family (fused dequant-into-aggregate); everything that
            # needs f32 rows declares itself f32-only instead of silently
            # widening the resident state back to 4 bytes/param.
            if store_mode != "arena":
                raise ValueError(
                    "arena_dtype='int8' requires store_mode='arena'; the "
                    "stack store keeps decoded f32 buffers"
                )
            if secure:
                raise ValueError(
                    "arena_dtype='int8' cannot run under secure "
                    "aggregation: mask-encoded fixed-point rows are f32-only"
                )
            if aggregation_rule != "fedavg":
                raise ValueError(
                    f"aggregation_rule={aggregation_rule!r} is f32-only: "
                    "order statistics sort full-precision rows and have no "
                    "fused dequantized form.  Use arena_dtype='f32' for "
                    "robust rules — see the support matrix in docs/ARENA.md"
                )
            if aggregate_fn is not None or masked_aggregate_fn is not None:
                raise ValueError(
                    "arena_dtype='int8' cannot honour a custom aggregate_fn/"
                    "masked_aggregate_fn: custom rules expect an f32 arena "
                    "buffer, not int8 values + scales"
                )
        self.arena_dtype = arena_dtype
        if store is not None and store_mode == "arena":
            # An explicit hash-map store would be silently bypassed by the
            # arena hot path — refuse the contradiction instead.
            raise ValueError(
                "store= is only honoured with store_mode='stack'; the arena "
                "mode keeps uploads in its device-resident ArenaStore"
            )
        self.protocol = protocol or SyncProtocol()
        self.selection = selection or SelectionPolicy()
        if aggregation_rule not in ("fedavg", "median", "trimmed_mean"):
            raise ValueError(
                "aggregation_rule must be 'fedavg', 'median' or "
                f"'trimmed_mean', got {aggregation_rule!r}"
            )
        if not isinstance(trim_k, int) or trim_k < 1:
            raise ValueError(f"trim_k must be an int >= 1, got {trim_k!r}")
        self.aggregation_rule = aggregation_rule
        self.trim_k = int(trim_k)
        if aggregation_rule != "fedavg":
            # Robust rules are order statistics: they have no secure-sum
            # form, no staleness-weighted form, and they replace (rather
            # than compose with) a custom aggregate function.
            if aggregate_fn is not None or masked_aggregate_fn is not None:
                raise ValueError(
                    "aggregation_rule= and a custom aggregate_fn/"
                    "masked_aggregate_fn are mutually exclusive"
                )
            if secure:
                raise ValueError(
                    f"aggregation_rule={aggregation_rule!r} cannot run under "
                    "secure aggregation: the controller only ever sees a "
                    "masked sum, and order statistics need the rows"
                )
            if (self.protocol.weighting() == "staleness"
                    or getattr(self.protocol, "aggregate_scope", None)
                    == "buffer"):
                raise ValueError(
                    f"aggregation_rule={aggregation_rule!r} is not defined "
                    "for staleness-weighted protocols (async / FedBuff): "
                    "the staleness discount has no order-statistic "
                    "analogue.  Use aggregation_rule='fedavg' there — see "
                    "the support matrix in docs/PROTOCOLS.md"
                )
        # A custom masked rule (or the wrapped custom aggregate_fn) opts out
        # of the rule-matched sharded reduction built in set_initial_model.
        self._masked_is_default = (
            aggregate_fn is None and masked_aggregate_fn is None
        )
        if aggregation_rule == "median":
            self.aggregate_fn = lambda stack, w: aggregation.coordinate_median(
                stack
            )
            self.masked_aggregate_fn = aggregation.masked_coordinate_median
        elif aggregation_rule == "trimmed_mean":
            _tk = self.trim_k
            self.aggregate_fn = lambda stack, w: aggregation.trimmed_mean(
                stack, _tk
            )
            self.masked_aggregate_fn = (
                lambda arena, w, m: aggregation.masked_trimmed_mean(
                    arena, w, m, _tk
                )
            )
        elif masked_aggregate_fn is not None:
            self.aggregate_fn = aggregate_fn or aggregation.fedavg
            self.masked_aggregate_fn = masked_aggregate_fn
        elif aggregate_fn is not None:
            self.aggregate_fn = aggregate_fn
            self.masked_aggregate_fn = (
                lambda arena, w, m: aggregate_fn(arena, w * m)
            )
        else:
            self.aggregate_fn = aggregation.fedavg
            self.masked_aggregate_fn = aggregation.masked_weighted_average
        self.server_opt = server_optimizer or make_server_optimizer("fedavg")
        self.store = store or ModelStore()
        self.store_mode = store_mode
        self.arena: ArenaStore | None = None
        self._arena_n_max = arena_n_max
        self._arena_row_align = arena_row_align
        self.arena_mesh = arena_mesh
        self.arena_axes = arena_axes
        if arena_mesh is not None and store_mode != "arena":
            raise ValueError("arena_mesh= requires store_mode='arena'")
        # Built lazily in set_initial_model when the arena is sharded.
        self._sharded_masked_fn: Callable | None = None
        self._sharded_staleness_fn: Callable | None = None
        # Quantized-arena (arena_dtype='int8') sharded reductions — mutually
        # exclusive with the f32 pair above.
        self._sharded_q8_fn: Callable | None = None
        self._sharded_staleness_q8_fn: Callable | None = None
        # Sparse-arena (sparse_mode='direct') scatter-accumulate reductions.
        self._sharded_topk_fn: Callable | None = None
        self._sharded_staleness_topk_fn: Callable | None = None
        self.channel = channel or Channel()
        if upload_codec is not None:
            self.channel.upload_codec = get_upload_codec(upload_codec)
        # Sparse (top-k) uplink: rows hold *deltas* (the learner sparsifies
        # its update against the shipped model, carrying the rest as an
        # error-feedback residual), so every aggregate commits
        # ``global_buffer + aggregated_delta``.  ``sparse_mode`` picks how
        # a sparse upload lands: 'densify' scatters it into the existing
        # dense row (every store/rule keeps working); 'direct' keeps an
        # (n_max, k) index/value arena resident and aggregates through the
        # masked scatter-accumulate (see docs/ARENA.md support matrix).
        self._topk = (
            getattr(self.channel.upload_codec, "codec_id", None) == "topk"
        )
        if sparse_mode not in ("direct", "densify"):
            raise ValueError(
                f"sparse_mode must be 'direct' or 'densify', "
                f"got {sparse_mode!r}"
            )
        self.sparse_mode = sparse_mode
        if self._topk:
            if secure:
                raise ValueError(
                    "upload_codec='topk' cannot run under secure "
                    "aggregation: the controller must densify and re-weight "
                    "sparse deltas, and the masked fixed-point rows admit "
                    "neither"
                )
            if not flat_uploads:
                raise ValueError(
                    "upload_codec='topk' requires flat_uploads=True: the "
                    "error-feedback residual lives learner-side against "
                    "the shipped wire manifest"
                )
            if aggregate_fn is not None or masked_aggregate_fn is not None:
                raise ValueError(
                    "upload_codec='topk' cannot honour a custom "
                    "aggregate_fn/masked_aggregate_fn: sparse rows hold "
                    "deltas, and custom rules expect full-parameter rows"
                )
        if sparse_mode == "direct":
            if not self._topk:
                raise ValueError(
                    "sparse_mode='direct' requires upload_codec='topk'"
                )
            if store_mode != "arena":
                raise ValueError(
                    "sparse_mode='direct' requires store_mode='arena'; the "
                    "stack store keeps dense decoded buffers"
                )
            if aggregation_rule != "fedavg":
                raise ValueError(
                    "sparse_mode='direct' supports only "
                    "aggregation_rule='fedavg'; the robust order-statistic "
                    "rules need dense rows — use sparse_mode='densify' "
                    f"(got {aggregation_rule!r})"
                )
            if arena_dtype != "f32":
                raise ValueError(
                    "sparse_mode='direct' keeps its own (n, k) sparse "
                    "arena; it cannot combine with "
                    f"arena_dtype={arena_dtype!r}"
                )
        # The unified observability surface: the controller adopts its
        # channel's registry, so every channel.* counter and every store/
        # controller instrument is reachable through this one handle.
        self.telemetry: Telemetry = self.channel.telemetry
        self.store.bind_telemetry(self.telemetry)
        self.secure = secure
        self.secure_seed = secure_seed
        self.profile_decay = profile_decay
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir
        # Admission control: a cheap screen at ingest.  Non-finite buffers
        # are rejected outright; once the EWMA of accepted update norms has
        # warmed up, outlier norms are clipped down to factor * EWMA.
        # Disabled under secure aggregation — the controller only ever sees
        # mask-encoded rows there, whose norms are meaningless.
        self.admission_control = bool(admission_control) and not secure
        self.admission_clip_factor = float(admission_clip_factor)
        self.admission_ewma_decay = float(admission_ewma_decay)
        self.admission_warmup = int(admission_warmup)
        self._adm_ewma: float | None = None
        self._adm_accepted = 0
        # Quarantine: per-learner decaying offense score.  Each rejected or
        # clipped upload adds 1 at the current round; the score decays by
        # quarantine_decay per round since the last offense, and a learner
        # is excluded from cohort selection while score >= threshold —
        # repeat offenders sit out, a single glitch does not.
        self.quarantine_threshold = float(quarantine_threshold)
        self.quarantine_decay = float(quarantine_decay)
        self._offenses: dict[str, tuple[float, int]] = {}
        # Hysteresis: entered at score >= threshold, released only once the
        # score decays below threshold/2 — without it a learner would enter
        # and be released by the very next round's decay tick.
        self._quarantined: set[str] = set()

        self._learners: dict[str, Learner] = {}
        self._learner_profiles: dict[str, LearnerProfile] = {}
        # Churn bookkeeping: lid -> round_id at dropout.  Profiles survive
        # deregistration so a rejoining learner resumes its EWMA histories
        # (reputation decayed over the absence).
        self._deregistered_at: dict[str, int] = {}
        self._c_dropouts = self.telemetry.counter("engine.faults.dropouts")
        self._c_rejoins = self.telemetry.counter("engine.faults.rejoins")
        # Admission / quarantine instrumentation (docs/OBSERVABILITY.md).
        self._c_rejected_nonfinite = self.telemetry.counter(
            "engine.uploads.rejected.nonfinite"
        )
        self._c_clipped = self.telemetry.counter("engine.uploads.clipped")
        # Quantized-arena fast paths (docs/OBSERVABILITY.md): uploads landed
        # in int8 form with no f32 materialization, and fused dequant-into-
        # aggregate reductions fired.
        self._c_quant_direct = self.telemetry.counter(
            "engine.uploads.quantized_direct"
        )
        self._c_fused_agg = self.telemetry.counter(
            "controller.aggregations.fused_q8"
        )
        # Sparse-uplink fast paths (docs/OBSERVABILITY.md): uploads landed
        # in the (n, k) sparse arena with no densification, and masked
        # scatter-accumulate reductions fired.
        self._c_sparse_direct = self.telemetry.counter(
            "engine.uploads.sparse_direct"
        )
        self._c_sparse_agg = self.telemetry.counter(
            "controller.aggregations.sparse_scatter"
        )
        self._c_quarantined = self.telemetry.counter("engine.quarantine.entered")
        self._g_quarantine = self.telemetry.gauge("engine.quarantine.active")
        self._store_lock = threading.Lock()

        self.global_params: Any = None
        self.global_buffer: jax.Array | None = None
        self.manifest: packing.Manifest | None = None
        self._server_state = None
        self.round_id = 0
        self.history: list[RoundTimings] = []
        # model-version state (continuous policy staleness accounting)
        self._model_version = 0
        self._learner_versions: dict[str, int] = {}
        # serialize-once dispatch state: one wire payload per model version
        self.flat_uploads = flat_uploads
        self._wire_lock = threading.Lock()
        self._wire_cache: tuple[tuple, Broadcast] | None = None
        # perf counters asserted by tests/test_dispatch.py: actual global-
        # model serializations triggered by dispatch, and the number of
        # uploads the controller had to flatten itself (0 on the fast path)
        self._c_dispatch_ser = self.telemetry.counter(
            "controller.dispatch_serializations"
        )
        self._c_fallback = self.telemetry.counter(
            "controller.upload_fallback_packs"
        )
        self._g_version = self.telemetry.gauge("controller.model_version")
        # The round engine owns the executor and the event loop; the
        # controller is its plumbing surface.  The journal is the engine's
        # flight recorder (an injected one wins over the sink/capacity knobs).
        if journal is None:
            journal = EventJournal(capacity=journal_capacity, sink=journal_sink)
        self.engine = RoundEngine(
            self, max_dispatch_workers=max_dispatch_workers, journal=journal
        )

    @property
    def dispatch_serializations(self) -> int:
        """Deprecated shim for ``telemetry.value('controller.dispatch_serializations')``."""
        return self._c_dispatch_ser.value

    @property
    def upload_fallback_packs(self) -> int:
        """Deprecated shim for ``telemetry.value('controller.upload_fallback_packs')``."""
        return self._c_fallback.value

    @property
    def journal(self) -> EventJournal:
        """The engine's flight recorder (``core/journal.EventJournal``)."""
        return self.engine.journal

    # ------------------------------------------------------------------ init
    def set_initial_model(self, params: Any) -> None:
        """Driver ships initial model tensors to the controller (Fig. 8).

        The controller's canonical model state is the flat numeric
        ``global_buffer`` + cached ``manifest``; ``global_params`` is
        normalized through one numeric roundtrip so the serialize-once
        broadcast (which reads the buffer) and the legacy per-send path
        (which reads the pytree) are bit-identical from round zero.
        """
        self.manifest = packing.build_manifest(params)
        self.global_buffer = packing.pack_numeric(params)
        self.global_params = packing.unpack_numeric(self.global_buffer, self.manifest)
        self._server_state = self.server_opt.init(self.global_buffer)
        self.invalidate_wire_cache()
        if self.store_mode == "arena":
            direct = self._topk and self.sparse_mode == "direct"
            self.arena = ArenaStore(
                num_params=max(1, int(self.global_buffer.shape[0])),
                n_max=max(self._arena_n_max, len(self._learners)),
                row_align=self._arena_row_align,
                mesh=self.arena_mesh,
                axes=self.arena_axes,
                telemetry=self.telemetry,
                arena_dtype="topk" if direct else self.arena_dtype,
                sparse_k=(
                    self.channel.upload_codec.k if direct else None
                ),
            )
            # Deterministic row order: rows follow *registration* order, not
            # first-upload arrival order, so arena aggregation order — and
            # with it the kill-and-resume parity contract — is reproducible.
            for lid in self._learners:
                self.arena.ensure_row(lid)
            if self.aggregation_rule == "trimmed_mean" and (
                2 * self.trim_k >= self.arena.n_max
            ):
                raise ValueError(
                    f"trim_k={self.trim_k} trims 2*trim_k={2 * self.trim_k} "
                    f"rows but the arena only holds {self.arena.n_max}; "
                    "every cohort would fall back to the untrimmed mean"
                )
            if self.arena.sharded:
                # Per-shard masked reductions over the column-sharded arena
                # (zero collectives; numerically identical to single-device).
                # Coordinate-wise rules all shard the same way, so the
                # reduction is matched to the configured aggregation_rule.
                # A user-supplied masked rule is honoured as-is — it runs on
                # the sharded buffer with whatever layout XLA infers.
                alpha = getattr(self.protocol, "staleness_alpha", 0.5)
                if self.arena.arena_dtype == "topk":
                    # Sparse arena: replicated (n, k) inputs, column-sharded
                    # (P,) output — each shard buckets the global indices
                    # into its own column window and scatters locally, so
                    # the compiled HLO stays collective-free.
                    self._sharded_topk_fn = (
                        aggregation.masked_fedavg_topk_sharded(
                            self.arena.mesh, self.arena.axes,
                            self.arena.padded_params,
                        )
                    )
                    self._sharded_staleness_topk_fn = (
                        aggregation.masked_staleness_topk_sharded(
                            self.arena.mesh, self.arena.axes,
                            self.arena.padded_params, alpha,
                        )
                    )
                elif self.arena_dtype == "int8":
                    # Quantized arena: the fused dequant-into-aggregate pair
                    # (values + scales share the column sharding; zero
                    # collectives).  Robust rules and custom fns were
                    # rejected at construction, so fedavg is the only rule.
                    self._sharded_q8_fn = aggregation.masked_fedavg_q8_sharded(
                        self.arena.mesh, self.arena.axes, self.arena.qgroup
                    )
                    self._sharded_staleness_q8_fn = (
                        aggregation.masked_staleness_q8_sharded(
                            self.arena.mesh, self.arena.axes, alpha,
                            self.arena.qgroup,
                        )
                    )
                elif self._masked_is_default:
                    if self.aggregation_rule == "median":
                        self._sharded_masked_fn = (
                            aggregation.masked_median_sharded(
                                self.arena.mesh, self.arena.axes
                            )
                        )
                    elif self.aggregation_rule == "trimmed_mean":
                        self._sharded_masked_fn = (
                            aggregation.masked_trimmed_mean_sharded(
                                self.arena.mesh, self.arena.axes, self.trim_k
                            )
                        )
                    else:
                        self._sharded_masked_fn = (
                            aggregation.masked_fedavg_sharded(
                                self.arena.mesh, self.arena.axes
                            )
                        )
                if (self.arena_dtype != "int8"
                        and self.arena.arena_dtype != "topk"):
                    self._sharded_staleness_fn = (
                        aggregation.masked_staleness_sharded(
                            self.arena.mesh, self.arena.axes, alpha
                        )
                    )
        for learner in self._learners.values():
            self._ship_manifest(learner)

    def _ship_manifest(self, learner: Learner) -> None:
        """Ship the wire contract (manifest + row width + channel) once.

        This is the flat-upload contract: with the manifest resident the
        learner packs its own uploads (padded to the arena row width) and —
        with the channel handle — sends them through the measured uplink
        (``Channel.upload``), so arrival is a codec decode plus a straight
        arena row write.  No-op until the initial model exists or when
        ``flat_uploads=False``.
        """
        if not self.flat_uploads or self.manifest is None:
            return
        pad_to = self.arena.padded_params if self.arena is not None else None
        learner.accept_manifest(self.manifest, pad_to=pad_to, channel=self.channel)

    def register_learner(self, learner: Learner) -> None:
        """Admit a learner to the federation (paper Fig. 8 join).

        A learner rejoining after :meth:`deregister_learner` keeps its
        accumulated EWMA profile — with the reputation estimate
        multiplicatively decayed over the rounds it was absent
        (churn-aware standing; counted in ``engine.faults.rejoins``).

        Thread contract: membership mutations are **not** synchronized
        with the engine loop — call :meth:`register_learner` /
        :meth:`deregister_learner` only while the engine loop is idle
        (between ``engine.run`` calls, as the stress harness does), or
        from within the loop thread itself.  Calling them from another
        thread while ``RoundEngine.run`` is executing races with arrival
        handling and dispatch.
        """
        lid = learner.learner_id
        rejoining = lid in self._deregistered_at
        self._learners[lid] = learner
        prof = self._learner_profiles.get(lid)
        if prof is None:
            self._learner_profiles[lid] = LearnerProfile(decay=self.profile_decay)
        elif rejoining:
            prof.decay_reputation(self.round_id - self._deregistered_at[lid])
        if rejoining:
            del self._deregistered_at[lid]
            self._c_rejoins.add(1)
        self._learner_versions[lid] = 0
        if self.arena is not None:
            self.arena.ensure_row(lid)
        self._ship_manifest(learner)

    def deregister_learner(self, learner_id: str) -> None:
        """Remove a learner mid-federation (dropout; paper Fig. 8 leave).

        Its store row is invalidated/discarded (a pending contribution
        leaves the aggregation set), its EWMA profile is *kept* so a rejoin
        resumes where it left off, and any upload still in flight lands as
        a tolerated, counted orphan (``engine.uploads.orphaned``) instead
        of crashing the engine loop.  Unknown ids are a no-op.

        Thread contract: this mutates engine-loop-owned state
        (``_learners``, the FedBuff buffer) without synchronization — see
        :meth:`register_learner`: only call it while the engine loop is
        idle (between ``engine.run`` calls) or from the loop thread.
        """
        if learner_id not in self._learners:
            return
        del self._learners[learner_id]
        self._deregistered_at[learner_id] = int(self.round_id)
        if self.arena is not None:
            if learner_id in self.arena._rows:
                self.arena.invalidate(learner_id)
        elif self.store_mode == "stack":
            with self._store_lock:
                self.store.discard(learner_id)
        # A buffered (ingested-but-unaggregated) FedBuff member can no
        # longer contribute: drop it from the pending buffer too.
        if learner_id in self.engine._buffer:
            self.engine._buffer.remove(learner_id)
        self._c_dropouts.add(1)

    @property
    def learner_ids(self) -> list[str]:
        """IDs of every registered learner, in registration order."""
        return list(self._learners)

    # -------------------------------------------------------------- dispatch
    def _broadcast(self) -> Broadcast:
        """The current model's shared wire payload, serialized at most once.

        Cached per (model version, codec): every dispatch within one version
        — train fan-out, eval fan-out, async re-dispatches between community
        updates — reuses the same read-only byte buffer, and the bytes come
        straight off ``global_buffer`` with the cached manifest (no pytree
        flattening, no manifest rebuild).  Aggregation bumps the version,
        which invalidates the cache on the next dispatch.
        """
        key = (self._model_version, id(self.channel.codec))
        with self._wire_lock:
            if self._wire_cache is None or self._wire_cache[0] != key:
                bc = self.channel.broadcast(
                    params=self.global_params,
                    buffer=self.global_buffer,
                    manifest=self.manifest,
                )
                self._c_dispatch_ser.add(1)
                self._wire_cache = (key, bc)
            return self._wire_cache[1]

    def invalidate_wire_cache(self) -> None:
        """Drop the cached broadcast, as if the model had just been re-published.

        The next dispatch pays one full serialization — benchmarks use this
        to measure the cold-cache dispatch cost deterministically.
        """
        with self._wire_lock:
            self._wire_cache = None

    # ------------------------------------------------------------ wire model
    def wire_time_s(self, learner_id: str) -> float:
        """Per-learner round-trip virtual wire estimate: downlink + uplink.

        Downlink is the broadcast envelope (``manifest.total_bytes``);
        uplink is the learner's last measured upload payload (recorded in
        its profile at ingest) or, before the first upload, the channel
        codec's modeled payload size for the padded row width.  The
        semi-sync policy subtracts this from its hyper-period budget so
        bandwidth-capped federations still finish inside the budget
        (``SemiSyncProtocol.size_task``; math in ``docs/ENGINE.md``).
        """
        if self.manifest is None:
            return 0.0
        down = int(self.manifest.total_bytes)
        prof = self._learner_profiles.get(learner_id)
        up = prof.get("upload_bytes") if prof is not None else None
        if up is None:
            n = (
                self.arena.padded_params
                if self.arena is not None
                else int(self.global_buffer.shape[0])
            )
            wire_nbytes = getattr(self.channel.upload_codec, "wire_nbytes", None)
            up = wire_nbytes(n) if wire_nbytes is not None else 4 * n
        return self.channel.round_trip_s(down, int(up), learner_id=learner_id)

    # ---------------------------------------------------------------- ingest
    def _upload_buffer(
        self,
        update: LocalUpdate,
        pad_to: int | None,
        with_norm: bool = False,
    ) -> jax.Array | tuple[jax.Array, jax.Array]:
        """The upload's decoded flat buffer, always off the measured uplink.

        Fast path: the learner already sent its packed row through
        ``Channel.upload`` and the update carries the wire envelope — decode
        it (one ``device_put`` + jitted codec decode).  Legacy paths (a bare
        pre-packed buffer, or ``flat_uploads=False`` where the controller
        must flatten the pytree itself — counted in ``upload_fallback_packs``)
        still cross the same measured half, with the controller standing in
        for the learner's send: every upload on every protocol is encoded,
        byte-accounted, and decoded through the channel's upload codec.

        With ``with_norm=True`` returns ``(buffer, norm)`` where ``norm``
        is the f32 L2 norm as an *unread device scalar*, fused into the
        same jitted decode — so the admission screen's single host sync
        covers an already-computed value instead of launching (and
        blocking on) a separate reduction per upload.
        """
        if update.upload is not None:
            return self.channel.recv_upload(update.upload, with_norm=with_norm)
        buffer = update.buffer
        if buffer is None:
            self._c_fallback.add(1)
            buffer = packing.pack_numeric(update.params, pad_to=pad_to)
        envelope = self.channel.upload(
            buffer, metadata={"learner_id": update.learner_id,
                              "round_id": update.round_id},
        )
        return self.channel.recv_upload(envelope, with_norm=with_norm)

    def _screen_norm(
        self, learner_id: str, norm: Any, round_id: int | None = None
    ) -> tuple[float | None, dict | None]:
        """The admission decision on the row's norm.

        ``norm`` is a device scalar (or a float).  Reading it back is the
        screen's one blocking host sync per upload, timed by the
        ``controller.screen`` span.

        A single NaN/inf anywhere in the row makes its norm non-finite
        (reject with :class:`UploadRejectedError`; counted in
        ``engine.uploads.rejected.nonfinite``), and once
        ``admission_warmup`` uploads have seeded the EWMA of accepted
        norms, a norm beyond ``admission_clip_factor`` times that EWMA
        must be rescaled down to the limit (counted in
        ``engine.uploads.clipped``).  Accepted (possibly clipped) norms
        feed the EWMA, so the envelope tracks the federation's own update
        scale.

        Returns ``(scale, clip_info)``: ``scale`` is the multiplicative
        clip factor the caller must apply to the row (``None`` when the
        row passes untouched), ``clip_info`` is ``None`` or
        ``{"norm": original, "limit": applied}``.
        """
        with self.telemetry.span("controller.screen", round=round_id,
                                 learner=learner_id):
            norm = float(norm)
        if not math.isfinite(norm):
            self._c_rejected_nonfinite.add(1)
            raise UploadRejectedError(learner_id, "nonfinite", norm)
        scale: float | None = None
        clip: dict | None = None
        if (
            self._adm_ewma is not None
            and self._adm_accepted >= self.admission_warmup
        ):
            limit = self.admission_clip_factor * self._adm_ewma
            if norm > limit > 0.0:
                scale = limit / norm
                self._c_clipped.add(1)
                clip = {"norm": norm, "limit": limit}
                norm = limit
        d = self.admission_ewma_decay
        self._adm_ewma = (
            norm if self._adm_ewma is None
            else d * self._adm_ewma + (1.0 - d) * norm
        )
        self._adm_accepted += 1
        return scale, clip

    def _screen_upload(
        self,
        learner_id: str,
        buffer: jax.Array,
        norm: jax.Array | None = None,
        round_id: int | None = None,
    ) -> tuple[jax.Array, dict | None]:
        """The admission screen: reject non-finite rows, clip norm outliers.

        One scalar — the f32 L2 norm of the decoded buffer — covers both
        checks (see :meth:`_screen_norm` for the decision itself).  The
        norm readback is the screen's single blocking host sync per
        upload; pass ``norm`` (an unread device scalar fused into the
        upload decode by ``recv_upload(..., with_norm=True)``) so that
        sync reads back an already-scheduled value instead of launching a
        fresh full-row reduction and waiting on it.

        Returns ``(buffer, clip_info)`` where ``clip_info`` is ``None`` or
        ``{"norm": original, "limit": applied}``.
        """
        if norm is None:
            norm = transport._row_norm(buffer)
        scale, clip = self._screen_norm(learner_id, norm, round_id)
        if scale is not None:
            buffer = buffer * jnp.asarray(scale, buffer.dtype)
        return buffer, clip

    def ingest(self, update: LocalUpdate) -> dict | None:
        """MarkTaskCompleted plumbing: decode the upload, store it, profile it.

        Called by the engine loop on every ``UploadArrived`` event.  Fast
        path (``flat_uploads``): the learner already packed its params at
        the arena's padded row width and sent them through the measured
        uplink, so arena mode is a codec decode plus a straight donated row
        write — zero pytree flattening, zero host concatenation on arrival.
        Otherwise the controller packs here (the legacy path, counted in
        ``upload_fallback_packs``) and routes the buffer through the same
        measured half.  Stack mode inserts the decoded buffer into the
        hash-map store either way.  The learner's EWMA profile absorbs the
        task's measured seconds-per-step and (fast path) wire payload size.

        With :attr:`admission_control` on, the decoded buffer passes the
        :meth:`_screen_upload` screen first: non-finite rows raise
        :class:`~repro.core.engine.UploadRejectedError` (nothing is stored;
        the engine journals the rejection and treats the learner as
        dropped for the round), and norm outliers are clipped before the
        row write.  The screen's norm is fused into the upload decode
        (``recv_upload(..., with_norm=True)``), so admission costs one
        host readback of an already-scheduled scalar instead of a
        blocking full-row reduction per upload.  Returns the screen's
        clip info (``None`` when the upload was stored untouched) so the
        engine can journal the clip.

        Quantized arenas (``arena_dtype='int8'``) take a *direct landing*
        when the wire codec matches the arena layout (int8 codec, same
        quantization group, row-width payload): the wire's int8 groups and
        f32 scales are split device-side and written straight into the
        arena — no f32 materialization, no requantization.  Norm
        screening happens in quantized form
        (:math:`\\sqrt{\\sum_g s_g^2 \\sum_i q_{g,i}^2}`) and clipping
        rescales the scales vector.  Counted in
        ``engine.uploads.quantized_direct``.

        Timed by the ``controller.ingest`` span; inside it the screen's
        readback is ``controller.screen`` and the row write (enqueued)
        ``controller.write``.
        """
        with self._span("controller.ingest", update):
            return self._ingest(update)

    def _span(self, name: str, update: LocalUpdate):
        """The span ``name`` carrying ``update``'s round and learner."""
        return self.telemetry.span(
            name, round=update.round_id, learner=update.learner_id
        )

    def _ingest(self, update: LocalUpdate) -> dict | None:
        clip: dict | None = None
        if self.store_mode == "arena":
            if self._sparse_direct_ok(update):
                idx, val, norm = self.channel.recv_upload_sparse(
                    update.upload
                )
                if self.admission_control:
                    scale, clip = self._screen_norm(
                        update.learner_id, norm, update.round_id
                    )
                    if scale is not None:
                        # Clipping a sparse row == rescaling its values
                        # (top-k indices are unique, so the value-vector
                        # norm *is* the row norm).
                        val = val * jnp.float32(scale)
                with self._span("controller.write", update):
                    self.arena.write_sparse(
                        update.learner_id,
                        idx,
                        val,
                        weight=float(update.num_examples),
                        version=float(
                            self._learner_versions.get(update.learner_id, 0)
                        ),
                    )
                self._c_sparse_direct.add(1)
            elif (self.arena is not None
                    and self.arena.arena_dtype == "topk"):
                raise ValueError(
                    "sparse_mode='direct' arena can only land registry "
                    "'topk' envelopes packed at the arena row width; got "
                    f"codec={getattr(update.upload, 'codec', None)!r}"
                )
            elif self._quant_direct_ok(update):
                q, scales, norm = self.channel.recv_upload_quantized(
                    update.upload, self.arena.padded_params
                )
                if self.admission_control:
                    scale, clip = self._screen_norm(
                        update.learner_id, norm, update.round_id
                    )
                    if scale is not None:
                        # Clipping a quantized row == rescaling its scales.
                        scales = scales * jnp.float32(scale)
                with self._span("controller.write", update):
                    self.arena.write_quantized(
                        update.learner_id,
                        q,
                        scales,
                        weight=float(update.num_examples),
                        version=float(
                            self._learner_versions.get(update.learner_id, 0)
                        ),
                    )
                self._c_quant_direct.add(1)
            else:
                if self.admission_control:
                    buffer, norm = self._upload_buffer(
                        update, pad_to=self.arena.padded_params,
                        with_norm=True,
                    )
                    buffer, clip = self._screen_upload(
                        update.learner_id, buffer, norm=norm,
                        round_id=update.round_id,
                    )
                else:
                    buffer = self._upload_buffer(
                        update, pad_to=self.arena.padded_params
                    )
                with self._span("controller.write", update):
                    self.arena.write(
                        update.learner_id,
                        buffer,
                        weight=float(update.num_examples),
                        version=float(
                            self._learner_versions.get(update.learner_id, 0)
                        ),
                    )
        else:
            if self.admission_control:
                buffer, norm = self._upload_buffer(
                    update, pad_to=None, with_norm=True
                )
                buffer, clip = self._screen_upload(
                    update.learner_id, buffer, norm=norm,
                    round_id=update.round_id,
                )
            else:
                buffer = self._upload_buffer(update, pad_to=None)
            with self._span("controller.write", update), self._store_lock:
                self.store.insert(
                    ModelRecord(
                        learner_id=update.learner_id,
                        round_id=update.round_id,
                        buffer=buffer,
                        num_examples=update.num_examples,
                        metadata={
                            **update.metrics,
                            "seconds_per_step": update.seconds_per_step,
                            "model_version": self._learner_versions.get(
                                update.learner_id, 0
                            ),
                        },
                    )
                )
        prof = self._learner_profiles[update.learner_id]
        prof.observe_step_time(update.seconds_per_step)
        if update.upload is not None:
            prof.observe_upload_bytes(update.upload.payload.nbytes)
        return clip

    def _sparse_direct_ok(self, update: LocalUpdate) -> bool:
        """True when the upload can land in the (n, k) sparse arena as-is.

        Requires a ``sparse_mode='direct'`` arena and a wire envelope from
        the registry ``topk`` codec whose payload was packed at the arena's
        padded row width (the ``flat_uploads`` fast path) — the arena row
        then *is* the wire's (index, value) stream, decoded device-side
        with the row norm fused into the same program.
        """
        if self.arena is None or self.arena.arena_dtype != "topk":
            return False
        env = update.upload
        return (
            env is not None
            and env.codec == "topk"
            and int(env.num_elements) == self.arena.padded_params
        )

    def _quant_direct_ok(self, update: LocalUpdate) -> bool:
        """True when the upload can land in the int8 arena without dequant.

        Requires an int8 arena, a wire envelope from the registry ``int8``
        codec whose quantization group matches the arena's ``qgroup``, and
        a payload already packed at the arena's padded row width (the
        ``flat_uploads`` fast path).  Anything else — raw codec, custom
        codec objects, group mismatch, legacy pytree uploads — falls back
        to the f32 decode, and :meth:`ArenaStore.write` requantizes.
        """
        if self.arena is None or self.arena.arena_dtype != "int8":
            return False
        env = update.upload
        return (
            env is not None
            and env.codec == "int8"
            and int(env.codec_params.get("group", 0)) == self.arena.qgroup
            and int(env.num_elements) == self.arena.padded_params
        )

    # ------------------------------------------------------------ quarantine
    def offense_score(self, learner_id: str) -> float:
        """The learner's decayed offense score as of the current round.

        Each rejected or clipped upload adds 1 at the round it happened;
        the stored score decays lazily by ``quarantine_decay`` per round
        elapsed since the last offense (no per-round sweep over the
        federation).
        """
        entry = self._offenses.get(learner_id)
        if entry is None:
            return 0.0
        score, last_round = entry
        delta = max(int(self.round_id) - int(last_round), 0)
        return score * (self.quarantine_decay ** delta)

    def note_offense(self, learner_id: str) -> bool:
        """Record one admission offense (rejected or clipped upload).

        Folds the decayed prior score plus 1 back into the table, stamped
        at the current round.  Returns True when this offense *newly*
        pushed the learner over ``quarantine_threshold`` (the engine
        journals a ``LearnerQuarantined`` event exactly then); counted in
        ``engine.quarantine.entered``, with the live population on the
        ``engine.quarantine.active`` gauge.
        """
        score = self.offense_score(learner_id) + 1.0
        self._offenses[learner_id] = (score, int(self.round_id))
        entered = (
            score >= self.quarantine_threshold
            and learner_id not in self._quarantined
        )
        if entered:
            self._quarantined.add(learner_id)
            self._c_quarantined.add(1)
        self._g_quarantine.set(len(self.quarantined_ids()))
        return entered

    def is_quarantined(self, learner_id: str) -> bool:
        """True while the learner sits inside the quarantine window.

        Entered at ``offense_score >= quarantine_threshold``; released
        (lazily, on this check) once decay drops the score below *half*
        the threshold — the hysteresis that makes the penalty an actual
        multi-round window instead of a single-round blip.  Quarantined
        learners are skipped by cohort selection
        (``RoundEngine._start_round``) — fail-open: if *every* available
        learner is quarantined the filter is waived rather than stalling
        the federation.
        """
        if learner_id not in self._quarantined:
            return False
        if self.offense_score(learner_id) < 0.5 * self.quarantine_threshold:
            self._quarantined.discard(learner_id)
            return False
        return True

    def quarantined_ids(self) -> list[str]:
        """Currently quarantined learner ids, in offense-table order."""
        return [lid for lid in self._offenses if self.is_quarantined(lid)]

    # ------------------------------------------------------------- aggregate
    def _commit(self, new_buffer: jax.Array) -> None:
        """Server-side optimization + global model swap + version bump.

        Sparse (topk) uplinks ship *deltas*, so the aggregate is a delta
        too: fold it onto the current global buffer first — the async-safe
        statement (the controller no longer holds each learner's base
        version), exactly equal to dense FedAvg when every cohort member
        trained from the same broadcast.
        """
        if self._topk:
            new_buffer = self.global_buffer + new_buffer
        self._server_state, new_buffer = self.server_opt.apply(
            self._server_state, self.global_buffer, new_buffer
        )
        new_buffer = jax.block_until_ready(new_buffer)
        self.global_buffer = new_buffer
        self.global_params = packing.unpack_numeric(new_buffer, self.manifest)
        self._model_version += 1
        self._g_version.set(self._model_version)

    def _reduce_and_commit(self, reduce_fn: Callable, *args: Any) -> float:
        """``reduce_fn(*args)``, then :meth:`_commit` of what it returns.

        The reduce runs under the ``controller.reduce`` span (it enqueues),
        the commit under ``controller.commit`` (it waits for the reduce and
        the server step on the device).  Returns the two spans' seconds.
        """
        with self.telemetry.span("controller.reduce",
                                 round=self.round_id) as reduce:
            new_buffer = reduce_fn(*args)
        with self.telemetry.span("controller.commit",
                                 round=self.round_id) as commit:
            self._commit(new_buffer)
        return reduce.seconds + commit.seconds

    def _mask_session_seed(self, epoch: int) -> int:
        """The per-epoch secure mask session (round id / model version key)."""
        from repro.core import secure as secure_mod

        return secure_mod.MaskSession(self.secure_seed, epoch).seed

    def aggregate_round(self, selected: list[str]) -> float:
        """Cohort aggregation for round-based policies (paper T4-T7).

        Arena mode: one masked reduction straight over the persistent device
        buffer — row writes already happened at arrival, so the round's
        critical path is just the reduce.  Stack mode: re-stack the stored
        buffers into an ``(N, P)`` array first (the legacy O(N·P) host copy).
        Secure mode sums mask-encoded fixed-point rows in a per-round mask
        session.  Commits the result; returns the aggregation seconds.
        """
        return self._reduce_and_commit(self._reduce_round, selected)

    def _reduce_round(self, selected: list[str]) -> jax.Array:
        """The reduce of :meth:`aggregate_round`, not yet committed."""
        if self.store_mode == "arena":
            new_buffer = self._aggregate_arena(selected)
        else:
            with self._store_lock:
                records = self.store.select_latest(list(selected))
            if not records:
                raise RuntimeError("no local models available to aggregate")

            if self.secure:
                from repro.core import secure as secure_mod

                buffers = [r.buffer for r in records]
                weights = [float(r.num_examples) for r in records]
                new_buffer = secure_mod.secure_fedavg(
                    buffers, weights,
                    base_seed=self._mask_session_seed(self.round_id),
                )
            else:
                stack = jnp.stack([r.buffer for r in records], axis=0)
                weights = jnp.asarray(
                    [float(r.num_examples) for r in records], jnp.float32
                )
                new_buffer = self.aggregate_fn(stack, weights)
        return new_buffer

    def _aggregate_arena(self, selected: list[str]) -> jax.Array:
        """Masked reduction over the arena restricted to the round's cohort."""
        arena = self.arena
        with arena.lock:
            if self.secure:
                from repro.core import secure as secure_mod

                rows, weights = [], []
                for lid in selected:
                    if lid in arena:
                        rows.append(arena.row_of(lid))
                        weights.append(arena.weight_of(lid))
                if not rows:
                    raise RuntimeError("no local models available to aggregate")
                # Sharded arena: sum the full padded width — padded_params is
                # divisible by n_shards by construction, so the column-sharded
                # int32 accumulator always engages (pairwise pads cancel
                # exactly whatever the width, and padding columns decode to
                # zero, so the [:num_params] slice is bit-identical to the
                # unpadded single-device sum).
                width = arena.padded_params if arena.sharded else arena.num_params
                return secure_mod.secure_fedavg_arena(
                    arena.buffer, rows, weights,
                    num_params=width,
                    base_seed=self._mask_session_seed(self.round_id),
                    out_sharding=arena.row_sharding,
                )[: arena.num_params]
            # Empty-cohort check from the arena's host-side row map: probing
            # the device mask (float(jnp.sum(mask))) would force a blocking
            # device round-trip onto every round's critical path.
            if arena.num_valid(list(selected)) == 0:
                raise RuntimeError("no local models available to aggregate")
            mask = arena.round_mask(list(selected))
            if arena.arena_dtype == "topk":
                # Masked scatter-accumulate straight off the (n, k) sparse
                # arena: the dense (N, P) stack is never built.
                if self._sharded_topk_fn is not None:
                    out = self._sharded_topk_fn(
                        arena.indices, arena.buffer, arena.weights, mask
                    )
                else:
                    out = aggregation.masked_fedavg_topk(
                        arena.indices, arena.buffer, arena.weights, mask,
                        arena.padded_params,
                    )
                self._c_sparse_agg.add(1)
                return out[: arena.num_params]
            if self.arena_dtype == "int8":
                # Fused dequant-into-aggregate: the reduce reads the int8
                # groups + scales directly, never materializing (N, P) f32.
                if self._sharded_q8_fn is not None:
                    out = self._sharded_q8_fn(
                        arena.buffer, arena.scales, arena.weights, mask
                    )
                else:
                    out = aggregation.masked_fedavg_q8(
                        arena.buffer, arena.scales, arena.weights, mask,
                        arena.qgroup,
                    )
                self._c_fused_agg.add(1)
                return out[: arena.num_params]
            # Built only for the rule-matched defaults (_masked_is_default);
            # a custom masked rule always takes the plain call below.
            if self._sharded_masked_fn is not None:
                out = self._sharded_masked_fn(arena.buffer, arena.weights, mask)
            else:
                out = self.masked_aggregate_fn(arena.buffer, arena.weights, mask)
            return out[: arena.num_params]

    def _staleness_q8(
        self, arena: ArenaStore, mask: jax.Array, alpha: float
    ) -> jax.Array:
        """Staleness-damped fused reduce over the quantized arena.

        Same math as ``masked_staleness_average`` with the dequant folded
        into the weighted sum; dispatches the column-sharded variant when
        the arena is sharded.  Counted in
        ``controller.aggregations.fused_q8``.
        """
        if self._sharded_staleness_q8_fn is not None:
            out = self._sharded_staleness_q8_fn(
                arena.buffer, arena.scales, arena.weights, arena.versions,
                jnp.float32(self._model_version), mask,
            )
        else:
            out = aggregation.masked_staleness_q8(
                arena.buffer, arena.scales, arena.weights, arena.versions,
                jnp.float32(self._model_version), mask, alpha,
                arena.qgroup,
            )
        self._c_fused_agg.add(1)
        return out[: arena.num_params]

    def _staleness_topk(
        self, arena: ArenaStore, mask: jax.Array, alpha: float
    ) -> jax.Array:
        """Staleness-damped scatter-accumulate over the sparse arena.

        Same math as ``masked_staleness_average`` restated over (index,
        value) streams; dispatches the column-sharded variant when the
        arena is sharded.  Counted in
        ``controller.aggregations.sparse_scatter``.
        """
        if self._sharded_staleness_topk_fn is not None:
            out = self._sharded_staleness_topk_fn(
                arena.indices, arena.buffer, arena.weights, arena.versions,
                jnp.float32(self._model_version), mask,
            )
        else:
            out = aggregation.masked_staleness_topk(
                arena.indices, arena.buffer, arena.weights, arena.versions,
                jnp.float32(self._model_version), mask,
                arena.padded_params, alpha,
            )
        self._c_sparse_agg.add(1)
        return out[: arena.num_params]

    def aggregate_community(self) -> float:
        """One staleness-weighted community update (the continuous policy).

        The arrival that triggered this update was already written in place
        by :meth:`ingest`, so there is no per-arrival stack rebuild — the
        paper's "community update request" cost is one fused kernel
        regardless of federation size.  With ``secure=True`` the update
        instead sums mask-encoded fixed-point rows weighted by the
        staleness-damped weights, inside a fresh per-epoch mask session
        keyed by the global model version (``core/secure.MaskSession``) —
        the controller still never sees an individual model.  Commits the
        result; returns the aggregation seconds.
        """
        return self._reduce_and_commit(self._reduce_community)

    def _reduce_community(self) -> jax.Array:
        """The reduce of :meth:`aggregate_community`, not yet committed."""
        alpha = getattr(self.protocol, "staleness_alpha", 0.5)
        if self.store_mode == "arena":
            arena = self.arena
            with arena.lock:
                if self.secure:
                    new_buffer = self._secure_community_arena(alpha)
                elif arena.arena_dtype == "topk":
                    new_buffer = self._staleness_topk(
                        arena, arena.mask, alpha
                    )
                elif self.arena_dtype == "int8":
                    new_buffer = self._staleness_q8(arena, arena.mask, alpha)
                elif self._sharded_staleness_fn is not None:
                    new_buffer = self._sharded_staleness_fn(
                        arena.buffer, arena.weights, arena.versions,
                        jnp.float32(self._model_version), arena.mask,
                    )[: arena.num_params]
                else:
                    new_buffer = aggregation.masked_staleness_average(
                        arena.buffer, arena.weights, arena.versions,
                        jnp.float32(self._model_version), arena.mask, alpha,
                    )[: arena.num_params]
        else:
            with self._store_lock:
                records = self.store.select_latest(None)  # all known models
            if not records:
                raise RuntimeError("no local models available to aggregate")
            if self.secure:
                from repro.core import secure as secure_mod

                weights = [
                    float(r.num_examples)
                    * (1.0 + self._model_version
                       - r.metadata.get("model_version", 0)) ** (-alpha)
                    for r in records
                ]
                new_buffer = secure_mod.secure_fedavg(
                    [r.buffer for r in records], weights,
                    base_seed=self._mask_session_seed(self._model_version),
                )
            else:
                stal = jnp.asarray(
                    [self._model_version - r.metadata.get("model_version", 0)
                     for r in records],
                    jnp.float32,
                )
                n_ex = jnp.asarray(
                    [float(r.num_examples) for r in records], jnp.float32
                )
                stack = jnp.stack([r.buffer for r in records], axis=0)
                w = aggregation.staleness_weights(n_ex, stal, alpha)
                new_buffer = self.aggregate_fn(stack, w)
        return new_buffer

    def aggregate_buffer(self, members: list[str]) -> float:
        """One FedBuff community update over exactly the buffered members.

        The continuous buffered-async policy
        (``BufferedAsyncProtocol``, ``aggregate_scope == "buffer"``) fires
        this with the K learner ids the engine drained from its arrival
        buffer: the reduce is restricted to those members' stored rows —
        staleness-damped like :meth:`aggregate_community`, but *not* over
        every valid row.  Members are folded in **registration order**
        (not arrival order), so the reduce is deterministic under any
        executor interleaving.  Commits the result; returns the seconds.
        """
        return self._reduce_and_commit(self._reduce_buffer, members)

    def _reduce_buffer(self, members: list[str]) -> jax.Array:
        """The reduce of :meth:`aggregate_buffer`, not yet committed."""
        alpha = getattr(self.protocol, "staleness_alpha", 0.5)
        wanted = set(members)
        ordered = [lid for lid in self._learners if lid in wanted]
        if not ordered:
            raise RuntimeError("no local models available to aggregate")
        if self.store_mode == "arena":
            arena = self.arena
            with arena.lock:
                if self.secure:
                    new_buffer = self._secure_community_arena(
                        alpha, members=ordered
                    )
                else:
                    if arena.num_valid(ordered) == 0:
                        raise RuntimeError(
                            "no local models available to aggregate"
                        )
                    mask = arena.round_mask(ordered)
                    if arena.arena_dtype == "topk":
                        new_buffer = self._staleness_topk(arena, mask, alpha)
                    elif self.arena_dtype == "int8":
                        new_buffer = self._staleness_q8(arena, mask, alpha)
                    elif self._sharded_staleness_fn is not None:
                        new_buffer = self._sharded_staleness_fn(
                            arena.buffer, arena.weights, arena.versions,
                            jnp.float32(self._model_version), mask,
                        )[: arena.num_params]
                    else:
                        new_buffer = aggregation.masked_staleness_average(
                            arena.buffer, arena.weights, arena.versions,
                            jnp.float32(self._model_version), mask, alpha,
                        )[: arena.num_params]
        else:
            with self._store_lock:
                records = self.store.select_latest(ordered)
            if not records:
                raise RuntimeError("no local models available to aggregate")
            if self.secure:
                from repro.core import secure as secure_mod

                weights = [
                    float(r.num_examples)
                    * (1.0 + self._model_version
                       - r.metadata.get("model_version", 0)) ** (-alpha)
                    for r in records
                ]
                new_buffer = secure_mod.secure_fedavg(
                    [r.buffer for r in records], weights,
                    base_seed=self._mask_session_seed(self._model_version),
                )
            else:
                stal = jnp.asarray(
                    [self._model_version - r.metadata.get("model_version", 0)
                     for r in records],
                    jnp.float32,
                )
                n_ex = jnp.asarray(
                    [float(r.num_examples) for r in records], jnp.float32
                )
                stack = jnp.stack([r.buffer for r in records], axis=0)
                w = aggregation.staleness_weights(n_ex, stal, alpha)
                new_buffer = self.aggregate_fn(stack, w)
        return new_buffer

    def _secure_community_arena(
        self, alpha: float, members: list[str] | None = None
    ) -> jax.Array:
        """Secure async update off the arena: staleness-damped masked sum.

        Staleness weights are *metadata* (example counts and model-version
        lags — the same inputs clear-text FedAvg weighting uses), so they
        are computed host-side from the arena's mirrors and folded into the
        fixed-point encoding learner-side, exactly like the FedAvg weights
        of the synchronous secure path.  Mask seeds come from the per-epoch
        session (one session per global model version).  ``members``
        restricts the sum to those learners' valid rows (the FedBuff
        buffered path); ``None`` keeps the community-wide default.
        """
        from repro.core import secure as secure_mod

        arena = self.arena
        valid = arena.valid_ids()
        ids = [lid for lid in members if lid in set(valid)] \
            if members is not None else valid
        rows, weights = [], []
        for lid in ids:
            row = arena.row_of(lid)
            stale = float(self._model_version) - arena.version_of(lid)
            rows.append(row)
            weights.append(arena.weight_of(lid) * (1.0 + stale) ** (-alpha))
        if not rows:
            raise RuntimeError("no local models available to aggregate")
        width = arena.padded_params if arena.sharded else arena.num_params
        return secure_mod.secure_fedavg_arena(
            arena.buffer, rows, weights,
            num_params=width,
            base_seed=self._mask_session_seed(self._model_version),
            out_sharding=arena.row_sharding,
        )[: arena.num_params]

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, directory: str | None = None,
                        step: int | None = None) -> str:
        """Persist the full federation state for crash-consistent resume.

        One ``.npz`` via ``repro.checkpoint``: the global model (packed
        buffer + manifest), server-optimizer state, the store contents
        (arena arrays or stack records), and a JSON meta block carrying the
        round/version counters, per-learner versions and EWMA profiles, the
        journal cursor, and a telemetry snapshot.  The journal's file sink
        is flushed first, so the JSONL on disk covers everything up to the
        checkpoint.  Called by ``engine.run(checkpoint_every=k)`` at round
        boundaries; ``directory`` defaults to :attr:`checkpoint_dir`,
        ``step`` to the current :attr:`round_id`.  Returns the file path.
        """
        from repro.checkpoint import checkpoint as ckpt

        directory = directory if directory is not None else self.checkpoint_dir
        if directory is None:
            raise ValueError("save_checkpoint needs a directory "
                             "(or Controller(checkpoint_dir=...))")
        if self.global_params is None:
            raise RuntimeError("set_initial_model() before save_checkpoint()")
        self.journal.flush()
        step = self.round_id if step is None else int(step)
        leaves, _ = jax.tree_util.tree_flatten(self._server_state)
        extras: dict[str, Any] = {
            f"server_state_{i}": leaf for i, leaf in enumerate(leaves)
        }
        meta: dict[str, Any] = {
            "round_id": int(self.round_id),
            "model_version": int(self._model_version),
            "learner_versions": {
                k: int(v) for k, v in self._learner_versions.items()
            },
            "aggregates_fired": int(self.engine.aggregates_fired),
            "profiles": {
                lid: {
                    "decay": prof.decay,
                    "observations": prof.observations,
                    "rep_observations": prof.rep_observations,
                    "data": jsonable(dict(prof)),
                }
                for lid, prof in self._learner_profiles.items()
            },
            "deregistered_at": {
                k: int(v) for k, v in self._deregistered_at.items()
            },
            "late_carry": list(self.engine._late_carry),
            "journal_cursor": int(self.journal.cursor),
            "protocol": type(self.protocol).__name__,
            "store_mode": self.store_mode,
            "secure": bool(self.secure),
            "aggregation_rule": self.aggregation_rule,
            "admission": {
                "ewma": self._adm_ewma,
                "accepted": int(self._adm_accepted),
            },
            "offenses": {
                lid: [float(score), int(rnd)]
                for lid, (score, rnd) in self._offenses.items()
            },
            "quarantined": sorted(self._quarantined),
            "telemetry": self.telemetry.snapshot(),
        }
        if getattr(self.protocol, "continuous", False):
            meta["pending_buffer"] = list(self.engine._buffer)
        if self.engine._pending_dispatch is not None:
            meta["pending_dispatch"] = list(self.engine._pending_dispatch)
        if self.arena is not None:
            st = self.arena.export_state()
            extras["arena_buffer"] = st["buffer"]
            extras["arena_weights"] = st["weights"]
            extras["arena_versions"] = st["versions"]
            extras["arena_valid"] = st["valid"]
            if st.get("scales") is not None:
                extras["arena_scales"] = st["scales"]
            if st.get("indices") is not None:
                extras["arena_indices"] = st["indices"]
            meta["arena_rows"] = {k: int(v) for k, v in st["rows"].items()}
            meta["arena_dtype"] = self.arena_dtype
        elif self.store_mode == "stack":
            records = self.store.export_records()
            meta["stack_records"] = [
                {
                    "learner_id": rec.learner_id,
                    "round_id": int(rec.round_id),
                    "num_examples": int(rec.num_examples),
                    "metadata": jsonable(rec.metadata),
                }
                for rec in records
            ]
            for j, rec in enumerate(records):
                extras[f"stackbuf_{j}"] = rec.buffer
        if self._topk:
            # The learner-side error-feedback residuals are federation
            # state: dropping them at resume silently re-sends mass the
            # carry already accounted for.  The engine checkpoints at
            # round boundaries after draining outstanding tasks, so the
            # residuals are quiescent here.
            meta["sparse_mode"] = self.sparse_mode
            residual_learners = []
            for lid, learner in self._learners.items():
                res = learner.export_residual()
                if res is not None:
                    extras[f"residual__{lid}"] = res
                    residual_learners.append(lid)
            meta["residual_learners"] = residual_learners
        return ckpt.save_checkpoint(
            directory, step, self.global_params,
            extra_arrays=extras, metadata=meta,
        )

    def restore(self, directory: str | None = None,
                step: int | None = None) -> dict:
        """Resume from a checkpoint written by :meth:`save_checkpoint`.

        Call on a freshly constructed controller with the *same*
        configuration (protocol, store mode, secure flag — validated
        against the checkpoint) and the same learners already registered.
        Restores the global model, server-optimizer state, round/version
        counters, learner profiles, store contents and the journal cursor;
        the next ``engine.run`` continues the interrupted workflow and —
        at matching data/batch schedules — produces bit-identical global
        models (``tests/test_checkpoint_resume.py``).  ``step=None`` picks
        the latest checkpoint.  Returns the checkpoint's meta block.
        """
        from repro.checkpoint import checkpoint as ckpt

        directory = directory if directory is not None else self.checkpoint_dir
        if directory is None:
            raise ValueError("restore needs a directory "
                             "(or Controller(checkpoint_dir=...))")
        params, extras, meta = ckpt.restore_checkpoint(directory, step)
        for key, mine in (
            ("protocol", type(self.protocol).__name__),
            ("store_mode", self.store_mode),
            ("secure", bool(self.secure)),
            ("aggregation_rule", self.aggregation_rule),
            ("arena_dtype", self.arena_dtype),
            ("sparse_mode", self.sparse_mode),
        ):
            if key in meta and meta[key] != mine:
                raise ValueError(
                    f"checkpoint was written with {key}={meta[key]!r}; "
                    f"this controller has {key}={mine!r}"
                )
        self.set_initial_model(params)
        # Server-optimizer state: graft the saved leaves onto the structure
        # of the freshly initialized state (same optimizer config ⇒ same
        # treedef), preserving python-scalar leaves as their native type.
        fresh_leaves, treedef = jax.tree_util.tree_flatten(self._server_state)
        restored_leaves = []
        for i, fresh in enumerate(fresh_leaves):
            saved = extras[f"server_state_{i}"]
            if isinstance(fresh, (bool, int, float)) and not hasattr(
                fresh, "dtype"
            ):
                restored_leaves.append(type(fresh)(saved.item()))
            else:
                restored_leaves.append(jnp.asarray(saved))
        self._server_state = jax.tree_util.tree_unflatten(
            treedef, restored_leaves
        )
        self.round_id = int(meta["round_id"])
        self._model_version = int(meta["model_version"])
        self._g_version.set(self._model_version)
        self._learner_versions.update(
            {k: int(v) for k, v in meta.get("learner_versions", {}).items()}
        )
        self.engine.aggregates_fired = int(meta.get("aggregates_fired", 0))
        for lid, saved_prof in meta.get("profiles", {}).items():
            prof = LearnerProfile(decay=float(saved_prof["decay"]))
            prof.observations = int(saved_prof["observations"])
            prof.rep_observations = int(saved_prof.get("rep_observations", 0))
            prof.update(saved_prof.get("data", {}))
            self._learner_profiles[lid] = prof
        self._deregistered_at = {
            k: int(v) for k, v in meta.get("deregistered_at", {}).items()
        }
        adm = meta.get("admission") or {}
        ewma = adm.get("ewma")
        self._adm_ewma = None if ewma is None else float(ewma)
        self._adm_accepted = int(adm.get("accepted", 0))
        self._offenses = {
            lid: (float(score), int(rnd))
            for lid, (score, rnd) in meta.get("offenses", {}).items()
        }
        self._quarantined = set(meta.get("quarantined", []))
        self._g_quarantine.set(len(self.quarantined_ids()))
        self.engine._late_carry = list(meta.get("late_carry", []))
        self.engine._buffer = list(meta.get("pending_buffer", []))
        if "pending_dispatch" in meta:
            self.engine._resume_dispatch = list(meta["pending_dispatch"])
        if self.arena is not None and "arena_rows" in meta:
            self.arena.restore_state(
                buffer=extras["arena_buffer"],
                weights=extras["arena_weights"],
                versions=extras["arena_versions"],
                valid=extras["arena_valid"],
                rows=meta["arena_rows"],
                scales=extras.get("arena_scales"),
                indices=extras.get("arena_indices"),
            )
        elif self.store_mode == "stack" and "stack_records" in meta:
            self.store.restore_records([
                ModelRecord(
                    learner_id=rec["learner_id"],
                    round_id=int(rec["round_id"]),
                    buffer=jnp.asarray(extras[f"stackbuf_{j}"]),
                    num_examples=int(rec["num_examples"]),
                    metadata=dict(rec.get("metadata", {})),
                )
                for j, rec in enumerate(meta["stack_records"])
            ])
        for lid in meta.get("residual_learners", []):
            learner = self._learners.get(lid)
            if learner is not None:
                learner.restore_residual(extras[f"residual__{lid}"])
        self.invalidate_wire_cache()
        self.journal.seek(int(meta.get("journal_cursor", 0)))
        return meta

    # -------------------------------------------------------------- lifecycle
    def shutdown(self) -> None:
        """Stop the engine's dispatch executor (waits for in-flight tasks)."""
        self.engine.shutdown()
