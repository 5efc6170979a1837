"""Long-lived streaming clustering service over envelope-bucketed NSPUs.

The serving pipeline, stage by stage (each independently testable):

* **admission** — ``submit`` validates a request against its design's
  compiled envelope *before anything touches JAX*: an unknown design, a
  series whose encoded width does not match any compiled bucket, or
  non-finite samples raise a structured ``RequestRejected`` — never a
  fresh trace.  Admission is also where overload control bites: a
  bounded pending queue (``max_pending``) sheds with
  ``reason='overloaded'`` and a retry-after hint, and a per-request
  deadline budget sheds with ``reason='deadline'`` when the predicted
  queue wait already exceeds it.
* **encode** — the series becomes a spike volley via the central encoder
  dispatch, compiled once per design shape (``encoding.encode_jit``),
  using the target design's gamma window, then fetched to the host.
* **bucket dispatch** — designs are packed into shared padding envelopes
  at construction (``backend.envelope_buckets``); a request rides the
  queue of its design's bucket and is batched with requests for *any*
  design in that bucket.
* **assign** — a full micro-batch (or a ``flush``-forced partial one,
  silent-padded to the compiled batch size through
  ``fused_column.pad_stream_silent``) dispatches ONE envelope-keyed AOT
  executable (``backend.assign_padded``).  After ``warmup`` the steady
  state performs zero XLA compiles: executables are keyed on
  shapes + statics, and the batch geometry never changes.  A request
  whose deadline expired while queued is shed at dispatch (a structured
  ``ServeShed``) — before its batch touches JAX.
* **re-fit** — every ``refit_every`` served requests per bucket, the live
  weights take an online-STDP pass over the most recent
  ``refit_window`` volleys each design served (``backend.fit_padded``).
  The candidate runs on a *copy* of the live block (the fused scan
  donates its weight operand, and a failed attempt must never destroy
  the last-good weights) and commits only if it returns finite weights
  within the watchdog budget; otherwise the attempt degrades down
  ``backend.lowering_ladder`` and, if every rung fails, the bucket
  enters **degraded mode** — serving continues from last-good weights
  while re-fit attempts back off exponentially
  (``backend.refit_backoff``).  Ragged buffers are silent-padded: for
  the positive thresholds the service enforces, a silent volley is an
  exact weight no-op, so the re-fit is bit-identical to an offline
  ``fit_padded`` resume on the same volleys.
* **stochastic STDP** — designs that learn with the hardware rule
  (``stdp.mode='stochastic'``: integer counters moved one LSB at a time,
  gated by a counter-based random stream) are served on the same path.
  Each design draws under its own stream key (from the service seed and
  its index); a bucket's designs share a next stream index, the
  *volley base*.  A committed re-fit of ``refit_epochs`` passes over the
  padded window of ``refit_window`` volleys draws indices ``base + e * W
  + n`` and advances the base by ``refit_epochs * W``, so a design's
  re-fits together draw what one fit over the joined windows would.  A
  failed, degraded or warm-up re-fit leaves the base where it was.
  Integer counters keep every batch's assign on the integer-grid kernel.
* **durability** — with ``durable_dir`` set, every committed re-fit is
  appended to a volley WAL and every ``snapshot_every`` re-fits the live
  weights snapshot atomically; ``ClusteringService.recover(dir)``
  replays WAL re-fits on top of the latest snapshot and restores weights
  (and, under stochastic STDP, the volley bases: each WAL record carries
  the stream keys and base its re-fit drew from, each snapshot the next
  bases) bit-identical to the uninterrupted service
  (``serve.durability``).

Failures quarantine per request: if a batch raises, each live request
re-runs alone against the same executable (assignment is per-volley
independent, so batch-mates' answers are bit-identical to the batched
run) and only the poisoned request surfaces a ``ServeFailure``.

The service is synchronous and single-threaded; "concurrent streams" are
interleaved logical streams multiplexed by the caller (see
``benchmarks/serve_bench.py``, which sustains 64+ of them).  Stage
timings feed a ``distributed.straggler.StepMonitor`` (stages labelled
``'assign'`` / ``'refit'``) so stalls are observable through ``stats()``.
Under a JAX profiler trace every stage is also a ``repro.obs`` span
(``serve.submit`` > ``serve.admit`` / ``serve.encode`` /
``serve.execute`` > ...; see ``docs/serving.md``).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import backend as backend_lib
from repro.core import column as column_lib
from repro.core import encoding
from repro.core import stdp as stdp_lib
from repro.core.types import ColumnConfig, TIME_DTYPE, column_config_from_dict
from repro.distributed.straggler import StepMonitor
from repro.kernels import fused_column
from repro.serve import durability

# Counters the service records (``repro.obs.count``, while a profiler
# runs): batches by the lowering their assign took (the Mosaic kernel on
# integer weights, else the reference body), and the live design-volleys
# (times epochs) each committed re-fit trained on.
ASSIGN_MOSAIC = "serve.assign_mosaic"
ASSIGN_REFERENCE = "serve.assign_reference"
REFIT_DESIGN_VOLLEYS = "serve.refit_design_volleys"
SERVE_COUNTERS = (ASSIGN_MOSAIC, ASSIGN_REFERENCE, REFIT_DESIGN_VOLLEYS)


class RequestRejected(Exception):
    """Structured admission failure — raised by ``submit`` before any JAX
    work happens, so a bad request can never trigger a trace storm.

    ``reason`` is machine-readable: ``'unknown-design'``, ``'shape'``,
    ``'envelope'`` (encoded width fits no compiled bucket),
    ``'non-finite'``, ``'overloaded'`` (bounded queue full),
    ``'deadline'`` (predicted wait exceeds the request's budget) or
    ``'draining'`` (the service is shutting down).  Load-shedding
    rejections carry ``retry_after_s``, a hint for when capacity should
    free up.
    """

    def __init__(self, reason: str, detail: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail
        self.retry_after_s = retry_after_s


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One served assignment: ``cluster`` is the earliest-firing neuron
    index of the target design, or its ``q`` when the volley was silent
    (unclustered)."""

    request_id: int
    design: str
    cluster: int
    latency_s: float


@dataclasses.dataclass(frozen=True)
class ServeFailure:
    """A quarantined request: the batch it rode failed, and so did its
    solo re-run.  Batch-mates are unaffected."""

    request_id: int
    design: str
    stage: str
    error: str


@dataclasses.dataclass(frozen=True)
class ServeShed:
    """A request shed at dispatch: admitted, but its deadline expired
    while it queued — no JAX work was spent on it."""

    request_id: int
    design: str
    reason: str
    waited_s: float


@dataclasses.dataclass(frozen=True)
class ServeStats:
    offered: int          # every submit() call, accepted or not
    submitted: int        # admitted into a queue
    served: int
    rejected: int         # admission rejections, total
    rejections: dict      # per-reason admission rejection counts
    shed: int             # admitted but deadline-expired at dispatch
    failed: int
    batches: int
    isolations: int
    refits: int           # committed online re-fits
    refit_failures: int   # re-fit windows where every ladder rung failed
    refit_stalls: int     # rung attempts discarded by the watchdog budget
    refit_retries: int    # failed rung attempts (raise, stall, non-finite)
    recoveries: int       # degraded buckets that re-fit successfully again
    degraded: int         # buckets currently serving from last-good weights
    stalls: int
    pending: int
    snapshots: int        # snapshots published this process
    wal_records: int      # WAL re-fits not yet covered by a snapshot
    replayed: int         # WAL re-fits replayed during recover()
    # per-bucket ExecutionPlan.meta() dicts — ({assign}, {fit|None}) per
    # bucket; 'source' says whether the roofline cost model or the
    # constants fallback chose each bucket's blocking
    plans: tuple = ()


class PendingRequest:
    """Handle returned by ``submit``; ``result()`` forces the request's
    bucket to flush if it is still queued."""

    def __init__(self, service: "ClusteringService", rid: int, design: str):
        self._service = service
        self.id = rid
        self.design = design
        self.outcome: Optional[
            Union[ServeResult, ServeFailure, ServeShed]
        ] = None

    @property
    def done(self) -> bool:
        return self.outcome is not None

    def result(self) -> Union[ServeResult, ServeFailure, ServeShed]:
        if self.outcome is None:
            self._service.flush(self.design)
        assert self.outcome is not None
        return self.outcome


class _Request:
    __slots__ = ("pending", "lane", "enc", "t_submit", "deadline")

    def __init__(self, pending, lane, enc, t_submit, deadline):
        self.pending = pending
        self.lane = lane
        self.enc = enc
        self.t_submit = t_submit
        self.deadline = deadline


class _Bucket:
    """One envelope bucket: live weights + compiled-shape metadata + queue
    + degraded-mode state."""

    def __init__(self, index, envelope, names, cfgs, w0, keys=None):
        self.index = index
        self.envelope = envelope  # (p_env, q_env, t_window)
        self.names = list(names)
        self.cfgs = list(cfgs)
        self.w = w0  # [Db, p_env, q_env] jnp — replaced by every re-fit
        self.thresholds = jnp.asarray(
            [c.neuron.threshold for c in cfgs], jnp.float32
        )
        self.t_maxes = jnp.asarray([c.t_max for c in cfgs], TIME_DTYPE)
        self.q_actives = jnp.asarray([c.q for c in cfgs], TIME_DTYPE)
        c0 = cfgs[0]
        self.fit_lowering = backend_lib.padded_lowering(c0.neuron.response)
        # the rung the last committed re-fit ran on ('' before the first)
        self.last_fit_lowering = ""
        # the whole block decides: one off-grid design makes the integer-
        # grid kernel a semantic switch for the bucket's shared executable
        self.asg_lowering = backend_lib.assign_lowering(
            c0.neuron.response, self.w
        )
        self.queue: list[_Request] = []
        self.buffers: list[list[np.ndarray]] = [[] for _ in cfgs]
        # ExecutionPlans for this bucket's two compiled shapes (filled in
        # by the service right after construction — it owns the batch /
        # re-fit geometry).  Reporting only: the backend re-derives the
        # identical plan inside assign_padded / fit_padded.
        self.asg_plan = None
        self.fit_plan = None
        self.served_since_refit = 0
        # degraded-mode state: after every ladder rung fails a re-fit
        # window, the bucket keeps serving from the last-good weights and
        # sits out `cooldown` re-fit windows before retrying
        self.degraded = False
        self.failed_refits = 0
        self.cooldown = 0
        self.last_refit_errors: list[str] = []
        # stochastic STDP: [Db, 2] i32 stream keys and the stream index the
        # next committed re-fit starts at (None: expected STDP)
        self.keys = keys
        self.v_base = 0
        self.cycle_exact = False


def _design_map(
    designs: Union[Mapping[str, ColumnConfig],
                   Sequence[tuple[str, ColumnConfig]]],
) -> dict[str, ColumnConfig]:
    if isinstance(designs, Mapping):
        return dict(designs)
    return dict(designs)


class ClusteringService:
    """Streaming front-end over a fleet of NSPU column designs.

    Args:
      designs: ``{name: ColumnConfig}`` (or ``(name, cfg)`` pairs).  All
        designs must share the fused statics (response, ``w_max``, WTA k,
        STDP mus/mode) — the same constraint as the sweep front-end — and
        every threshold must be positive (the silent-volley no-op that
        partial batches and ragged re-fits rely on).  Expected- and
        stochastic-mode STDP are both served.
      encoder: ``'latency'`` or ``'onoff'`` (admission uses
        ``encoding.encoded_width`` to pin series length to design width).
      batch_size: requests per compiled assignment micro-batch; a full
        queue auto-executes, ``flush`` silent-pads a partial one.
      refit_every: served requests per bucket between online re-fits
        (0 disables re-fitting).
      refit_window: volleys per design each re-fit trains on (the most
        recent served; fixes the re-fit executable's shape).
      refit_epochs: STDP epochs per re-fit.
      weights: optional ``{name: [p, q] array}`` initial weights (e.g.
        from an offline ``cluster_time_series`` fit); designs without an
        entry draw ``column.init_params`` from ``fold_in(seed, index)``.
        Stochastic designs take integer counters only.
      max_pending: bound on the total queued (unexecuted) requests across
        all buckets; beyond it ``submit`` sheds with
        ``RequestRejected(reason='overloaded')`` and a retry-after hint.
        ``None`` (default) leaves admission unbounded.
      default_deadline_s: deadline budget applied to every request that
        does not pass its own ``deadline_s``; a request whose predicted
        queue wait exceeds its budget is shed at admission
        (``reason='deadline'``), and one whose budget expires while
        queued is shed at dispatch (a ``ServeShed`` outcome) — either
        way, before any JAX work is spent on it.
      refit_budget_s: watchdog budget for one re-fit attempt; an attempt
        exceeding it is discarded as stalled (the rung's result is
        thrown away) and the ladder moves on.  ``None`` disables the
        budget.
      durable_dir: directory for crash durability (snapshots + re-fit
        WAL — see ``serve.durability``).  Must be fresh; resume an
        existing one with ``ClusteringService.recover(dir)``.
      snapshot_every: committed re-fits between snapshots (with
        ``durable_dir``); the WAL covers the gap.
      monitor: a ``StepMonitor`` for stage timings (one is created by
        default; stalls surface in ``stats()``).
    """

    def __init__(
        self,
        designs,
        *,
        encoder: str = "latency",
        batch_size: int = 16,
        refit_every: int = 64,
        refit_window: int = 32,
        refit_epochs: int = 1,
        seed: int = 0,
        weights: Optional[Mapping[str, np.ndarray]] = None,
        waste_cap: Optional[float] = None,
        max_bucket: Optional[int] = None,
        max_pending: Optional[int] = None,
        default_deadline_s: Optional[float] = None,
        refit_budget_s: Optional[float] = None,
        durable_dir: Optional[str] = None,
        snapshot_every: int = 4,
        monitor: Optional[StepMonitor] = None,
        _attach: bool = False,
    ):
        cfg_map = _design_map(designs)
        if not cfg_map:
            raise ValueError("service needs at least one design")
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if refit_window < 1:
            raise ValueError("refit_window must be >= 1")
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None)")
        if snapshot_every < 1:
            raise ValueError("snapshot_every must be >= 1")
        # unknown encoder raises here, at construction
        encoding.encoded_width(1, encoder)
        self.encoder = encoder
        self.batch_size = int(batch_size)
        self.refit_every = int(refit_every)
        self.refit_window = int(refit_window)
        self.refit_epochs = int(refit_epochs)
        self.max_pending = max_pending
        self.default_deadline_s = default_deadline_s
        self.refit_budget_s = refit_budget_s
        self.snapshot_every = int(snapshot_every)
        self._seed = int(seed)
        self._waste_cap = waste_cap
        self._max_bucket = max_bucket
        self.monitor = monitor if monitor is not None else StepMonitor(
            threshold=4.0, warmup=3
        )

        names = list(cfg_map)
        cfgs = [cfg_map[n] for n in names]
        c0 = cfgs[0]
        for n, c in zip(names, cfgs):
            fused_column.check_fusable(
                c, backend_lib.padded_lowering(c.neuron.response)
            )
            if c.neuron.threshold <= 0:
                raise ValueError(
                    f"design {n!r}: threshold must be > 0 — the service "
                    "pads partial batches and ragged re-fit windows with "
                    "silent volleys, which are weight no-ops only above "
                    "threshold 0"
                )
            same = (
                c.neuron.response == c0.neuron.response
                and c.neuron.w_max == c0.neuron.w_max
                and c.wta == c0.wta
                and c.stdp == c0.stdp
            )
            if not same:
                raise ValueError(
                    f"design {n!r}: all designs must share response/w_max/"
                    "WTA/STDP statics (one compiled program per bucket)"
                )
        self._cfgs = cfg_map
        self._statics = dict(
            w_max=c0.neuron.w_max, wta_k=c0.wta.k,
            mu_capture=c0.stdp.mu_capture, mu_backoff=c0.stdp.mu_backoff,
            mu_search=c0.stdp.mu_search,
            stabilize=c0.stdp.stabilizer == "half",
            response=c0.neuron.response,
        )
        self._rule = c0.stdp.mode
        self.stochastic = self._rule == "stochastic"

        # ---- bucket construction: pack design shapes into envelopes and
        # assemble each bucket's live weight block host-side (the sweep
        # idiom), per-design init keys folded from the service seed
        shapes = [(c.p, c.q, c.t_max) for c in cfgs]
        buckets = backend_lib.envelope_buckets(shapes, waste_cap, max_bucket)
        key = jax.random.key(seed)
        stream_root, _ = jax.random.split(key)
        self._buckets: list[_Bucket] = []
        self._route: dict[str, tuple[_Bucket, int]] = {}
        for bi, (env, members) in enumerate(buckets):
            p_env, q_env, t_window = env
            w0 = np.zeros((len(members), p_env, q_env), np.float32)
            for lane, i in enumerate(members):
                c = cfgs[i]
                if weights is not None and names[i] in weights:
                    wi = np.asarray(weights[names[i]], np.float32)
                    if wi.shape != (c.p, c.q):
                        raise ValueError(
                            f"weights[{names[i]!r}]: expected shape "
                            f"{(c.p, c.q)}, got {wi.shape}"
                        )
                    if self.stochastic and not np.array_equal(
                        wi, np.round(wi)
                    ):
                        raise ValueError(
                            f"weights[{names[i]!r}]: stochastic STDP moves "
                            "integer counters — initial weights must be "
                            "integral"
                        )
                else:
                    wi = np.asarray(
                        column_lib.init_params(
                            jax.random.fold_in(key, i), c
                        )["w"]
                    )
                w0[lane, : c.p, : c.q] = wi
            keys = None
            if self.stochastic:
                keys = jnp.stack([
                    stdp_lib.stream_key(jax.random.fold_in(stream_root, i))
                    for i in members
                ])
            bucket = _Bucket(
                bi, env, [names[i] for i in members],
                [cfgs[i] for i in members], jnp.asarray(w0), keys,
            )
            # the 'cycle' solver joins a stochastic bucket's ladder as its
            # last rung: unit steps keep integer counters integral, so it
            # stays bit-identical to the fused rungs
            bucket.cycle_exact = self.stochastic and all(
                backend_lib.cycle_exact(cfgs[i], w0[lane, : cfgs[i].p,
                                                    : cfgs[i].q])
                for lane, i in enumerate(members)
            )
            # record which blocking policy this bucket's executables will
            # resolve to (cost-model plan when a calibration is active,
            # constants otherwise) — assign_padded / fit_padded re-derive
            # the same plan from the same inputs at dispatch time
            bucket.asg_plan = backend_lib.execution_plan(
                "assign", bucket.asg_lowering, len(members),
                p_env, q_env, t_window, self.batch_size, 1,
                w_max=self._statics["w_max"],
                response=self._statics["response"],
            )
            if self.refit_every > 0:
                bucket.fit_plan = backend_lib.execution_plan(
                    "fit", bucket.fit_lowering, len(members),
                    p_env, q_env, t_window,
                    self.refit_window, self.refit_epochs,
                    w_max=self._statics["w_max"],
                    response=self._statics["response"],
                )
            self._buckets.append(bucket)
            for lane, i in enumerate(members):
                self._route[names[i]] = (bucket, lane)

        self._next_id = 0
        self._offered = 0
        self._submitted = 0
        self._served = 0
        self._rejected = 0
        self._rejections: dict[str, int] = {}
        self._shed = 0
        self._failed = 0
        self._batches = 0
        self._isolations = 0
        self._refits = 0
        self._refit_failures = 0
        self._refit_stalls = 0
        self._refit_retries = 0
        self._recoveries = 0
        self._snapshots = 0
        self._replayed = 0
        self._refit_seq = 0
        self._batch_ewma: Optional[float] = None
        self._draining = False

        # ---- durability: fresh directories get meta + WAL header + a
        # seq-0 snapshot of the initial weights; recover() attaches to an
        # existing directory, restores the latest snapshot and replays
        # the WAL tail (bit-identical — weights only ever mutate at
        # committed re-fits, and each WAL record is one committed
        # re-fit's exact input window)
        self._store: Optional[durability.DurableStore] = None
        if durable_dir is not None:
            spec = self._replay_spec()
            fingerprint = durability.service_fingerprint(spec)
            store = durability.DurableStore(durable_dir)
            if _attach:
                step, records = store.attach(fingerprint)
                tree, _ = store.ckpt.restore(
                    self._snapshot_tree(), step=step
                )
                for b, wb in zip(self._buckets, tree):
                    self._commit_weights(b, wb)
                if self.stochastic:
                    for b, base in zip(self._buckets, np.asarray(tree[-1])):
                        b.v_base = int(base)
                self._store = store
                self._refit_seq = step
                for rec in records:
                    self._replay(rec)
            else:
                store.create(
                    {
                        "version": durability.DURABLE_VERSION,
                        "fingerprint": fingerprint,
                        "spec": spec,
                        "serving": {
                            "batch_size": self.batch_size,
                            "refit_every": self.refit_every,
                            "snapshot_every": self.snapshot_every,
                            "max_pending": self.max_pending,
                            "default_deadline_s": self.default_deadline_s,
                            "refit_budget_s": self.refit_budget_s,
                        },
                    },
                    self._snapshot_tree(),
                )
                self._store = store

    # -------------------------------------------------------- durability
    def _replay_spec(self) -> dict:
        """The replay-relevant service identity: everything that pins
        bucket structure, init weights and re-fit semantics — NOT the
        serving knobs (batch size, deadlines...), which a recovered
        service may legitimately change."""
        return {
            "names": list(self._cfgs),
            "cfgs": [dataclasses.asdict(c) for c in self._cfgs.values()],
            "encoder": self.encoder,
            "seed": self._seed,
            "refit_window": self.refit_window,
            "refit_epochs": self.refit_epochs,
            "waste_cap": self._waste_cap,
            "max_bucket": self._max_bucket,
            "statics": {
                k: v for k, v in self._statics.items()
            },
        }

    @classmethod
    def recover(cls, durable_dir: str, *, monitor: Optional[StepMonitor] =
                None, **overrides) -> "ClusteringService":
        """Rebuild a service from its durable directory: reconstruct the
        fleet from ``meta.json``, restore the latest published snapshot,
        and replay the WAL's committed re-fits on top — weights come back
        **bit-identical** to the uninterrupted service at its last
        committed re-fit (a kill loses at most the re-fit in flight, and
        the served-but-unrefit volley buffers).

        Serving knobs (``batch_size``, ``max_pending``, deadlines, ...)
        default to the values recorded at creation; pass ``overrides`` to
        change them.  Call ``warmup()`` on the recovered service before
        taking traffic, as usual.
        """
        meta = durability.DurableStore(durable_dir).load_meta()
        if meta.get("version") != durability.DURABLE_VERSION:
            raise ValueError(
                f"{durable_dir}: durable format version "
                f"{meta.get('version')} != {durability.DURABLE_VERSION}"
            )
        spec = meta["spec"]
        designs = {
            n: column_config_from_dict(d)
            for n, d in zip(spec["names"], spec["cfgs"])
        }
        kwargs = dict(meta.get("serving", {}))
        kwargs.update(
            encoder=spec["encoder"], seed=spec["seed"],
            refit_window=spec["refit_window"],
            refit_epochs=spec["refit_epochs"],
            waste_cap=spec["waste_cap"], max_bucket=spec["max_bucket"],
        )
        kwargs.update(overrides)
        return cls(
            designs, monitor=monitor, durable_dir=durable_dir,
            _attach=True, **kwargs,
        )

    def _replay(self, rec: dict) -> None:
        """Apply one WAL re-fit record — same ladder, same commit path as
        the live re-fit, no budget (a recovering process pays compiles
        here) and no re-logging.  A stochastic record draws under the
        keys and from the base it carries, and moves the bucket's base
        past its window."""
        bucket = self._buckets[rec["bucket"]]
        xs = np.asarray(rec["xs"], np.int32)
        stream = None
        if self.stochastic:
            stream = (jnp.asarray(rec["keys"], jnp.int32), int(rec["v_base"]))
            bucket.v_base = stream[1] + self._stream_advance()
        w_new, _low, errors = self._attempt_window(
            bucket, xs, ladder=self._ladder(bucket), stream=stream,
            label="replay", enforce_budget=False,
        )
        if w_new is None:
            # the record committed in a prior life; failing here means the
            # environment changed — keep serving from the snapshot weights
            warnings.warn(
                f"WAL replay: re-fit seq {rec['seq']} failed every rung "
                f"({errors}); continuing from pre-record weights"
            )
            self._refit_failures += 1
        else:
            self._commit_weights(bucket, w_new)
        self._refit_seq = int(rec["seq"])
        self._replayed += 1

    def _snapshot_tree(self) -> list:
        """What a snapshot holds: each bucket's live block, and under
        stochastic STDP one more leaf, the buckets' next volley bases."""
        tree = [b.w for b in self._buckets]
        if self.stochastic:
            tree.append(np.asarray([b.v_base for b in self._buckets],
                                   np.int32))
        return tree

    def _snapshot(self) -> None:
        if self._store is None:
            return
        self._store.snapshot(self._refit_seq, self._snapshot_tree())
        self._snapshots += 1

    def drain(self) -> ServeStats:
        """Graceful shutdown: stop admission (``submit`` now sheds with
        ``reason='draining'``), serve every queued request, and publish a
        final snapshot so recovery replays nothing.  Idempotent; the
        SIGTERM path of ``launch/serve_tnn.py`` calls this."""
        self._draining = True
        self.flush()
        self._snapshot()
        return self.stats()

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------- intro
    def designs(self) -> tuple[str, ...]:
        return tuple(self._cfgs)

    def buckets(self) -> list[dict]:
        """Bucket-dispatch summary: one dict per compiled envelope."""
        return [
            {
                "envelope": b.envelope,
                "designs": tuple(b.names),
                "batch_shape": (self.batch_size, len(b.names), b.envelope[0]),
                "refit_shape": (
                    self.refit_window, len(b.names), b.envelope[0]
                ),
                "fit_lowering": b.fit_lowering,
                "last_fit_lowering": b.last_fit_lowering,
                "asg_lowering": b.asg_lowering,
                "stream_base": b.v_base if self.stochastic else None,
                "degraded": b.degraded,
                "cooldown": b.cooldown,
                "assign_plan": b.asg_plan.meta() if b.asg_plan else None,
                "fit_plan": b.fit_plan.meta() if b.fit_plan else None,
            }
            for b in self._buckets
        ]

    def weights(self, design: str) -> np.ndarray:
        """Copy of a design's live weights, cropped to its own (p, q)."""
        bucket, lane = self._route[design]
        c = self._cfgs[design]
        return np.asarray(bucket.w[lane, : c.p, : c.q])

    def stats(self) -> ServeStats:
        return ServeStats(
            offered=self._offered,
            submitted=self._submitted,
            served=self._served,
            rejected=self._rejected,
            rejections=dict(self._rejections),
            shed=self._shed,
            failed=self._failed,
            batches=self._batches,
            isolations=self._isolations,
            refits=self._refits,
            refit_failures=self._refit_failures,
            refit_stalls=self._refit_stalls,
            refit_retries=self._refit_retries,
            recoveries=self._recoveries,
            degraded=sum(1 for b in self._buckets if b.degraded),
            stalls=len(self.monitor.events),
            pending=sum(len(b.queue) for b in self._buckets),
            snapshots=self._snapshots,
            wal_records=self._store.pending if self._store else 0,
            replayed=self._replayed,
            plans=tuple(
                (
                    b.asg_plan.meta() if b.asg_plan else None,
                    b.fit_plan.meta() if b.fit_plan else None,
                )
                for b in self._buckets
            ),
        )

    # ------------------------------------------------------------ warmup
    def warmup(self) -> dict:
        """Compile (or disk-load) every executable and warm every shape
        the steady state dispatches, so traffic performs ZERO XLA compiles
        afterwards.

        Per design: the jitted encode of its series length and gamma
        window.  Per bucket: the batch-shaped assignment executable and the
        window-shaped re-fit executable become resident via the backend
        ``warm_*`` pre-compilers, then one all-silent batch and one
        all-silent re-fit run end-to-end through the real serving path —
        silent volleys assign to "unclustered" (discarded) and are exact
        weight no-ops, so warmup changes no answers and no weights while
        exercising the same ops as live traffic.  Warm-up draws no
        stream indices: a stochastic bucket's base stays where it was.
        """
        t0 = time.perf_counter()
        hot = 0
        for name, c in self._cfgs.items():
            length = c.p if self.encoder == "latency" else c.p // 2
            np.asarray(
                encoding.encode_jit(np.zeros(length), c.t_max, self.encoder)
            )
        for b in self._buckets:
            db = len(b.names)
            p_env, q_env, t_window = b.envelope
            hot += backend_lib.warm_assign_padded(
                db, p_env, q_env, self.batch_size,
                t_window=t_window, wta_k=self._statics["wta_k"],
                response=self._statics["response"],
                lowering=b.asg_lowering, w_max=self._statics["w_max"],
            )
            self._assign(b, self._silent_batch(b))  # warm eager shapes
            if self.refit_every > 0:
                hot += backend_lib.warm_fit_padded(
                    db, p_env, q_env, self.refit_window,
                    t_window=t_window, w_max=self._statics["w_max"],
                    wta_k=self._statics["wta_k"],
                    stabilize=self._statics["stabilize"],
                    response=self._statics["response"],
                    epochs=self.refit_epochs, lowering=b.fit_lowering,
                    stochastic=self.stochastic,
                )
                self._refit(b, warm=True)  # silent window: exact no-op
        return {
            "buckets": len(self._buckets),
            "already_resident": hot,
            "seconds": time.perf_counter() - t0,
        }

    # --------------------------------------------------------- admission
    def _reject(self, reason: str, detail: str,
                retry_after_s: Optional[float] = None) -> None:
        self._rejected += 1
        self._rejections[reason] = self._rejections.get(reason, 0) + 1
        raise RequestRejected(reason, detail, retry_after_s)

    def _batch_seconds(self) -> float:
        """Recent EWMA of one batched assignment's wall time (0.0 until
        the first post-warmup batch lands)."""
        return self._batch_ewma if self._batch_ewma is not None else 0.0

    def _wait_estimate_s(self, bucket: _Bucket) -> float:
        """Predicted queue wait for a request admitted to ``bucket`` now:
        batches ahead of it (its own included) times the recent batch
        time."""
        batches_ahead = len(bucket.queue) // self.batch_size + 1
        return batches_ahead * self._batch_seconds()

    def submit(self, series, design: str,
               deadline_s: Optional[float] = None) -> PendingRequest:
        """Admit one series for ``design``; raises ``RequestRejected`` on
        admission failure (including load shedding), returns a
        ``PendingRequest`` otherwise.  A full bucket queue executes
        immediately (the returned handle is then already ``done``).

        ``deadline_s`` is this request's latency budget (defaults to the
        service-wide ``default_deadline_s``): the request is shed at
        admission if the predicted queue wait already exceeds it, and at
        dispatch if it expired while queued.
        """
        route = self._route.get(design)
        with obs.span("serve.submit", request=self._next_id, design=design,
                      bucket=-1 if route is None else route[0].index):
            with obs.span("serve.admit"):
                bucket, lane, cfg, x, deadline = self._admit(
                    series, design, deadline_s
                )
            with obs.span("serve.encode"):
                enc = np.asarray(
                    encoding.encode_jit(x, cfg.t_max, self.encoder)
                )
            pending = PendingRequest(self, self._next_id, design)
            self._next_id += 1
            self._submitted += 1
            bucket.queue.append(
                _Request(pending, lane, enc, time.perf_counter(), deadline)
            )
            if len(bucket.queue) >= self.batch_size:
                self._execute(bucket)
        return pending

    def _admit(self, series, design: str, deadline_s: Optional[float]):
        """Admission: drain and overload checks, route, width, finiteness
        and the deadline estimate; returns ``(bucket, lane, cfg, x,
        deadline)`` or raises ``RequestRejected``."""
        self._offered += 1
        if self._draining:
            self._reject(
                "draining", "service is draining; no new work accepted"
            )
        if self.max_pending is not None:
            pending = sum(len(b.queue) for b in self._buckets)
            if pending >= self.max_pending:
                self._reject(
                    "overloaded",
                    f"{pending} pending requests >= max_pending="
                    f"{self.max_pending}",
                    retry_after_s=(
                        pending / self.batch_size
                    ) * self._batch_seconds(),
                )
        route = self._route.get(design)
        if route is None:
            self._reject(
                "unknown-design",
                f"{design!r} not served (have {sorted(self._route)})",
            )
        bucket, lane = route
        cfg = self._cfgs[design]
        x = np.asarray(series, np.float64)
        if x.ndim != 1:
            self._reject(
                "shape", f"expected one series [L], got shape {x.shape}"
            )
        width = encoding.encoded_width(x.shape[0], self.encoder)
        if width != cfg.p:
            self._reject(
                "envelope",
                f"series of length {x.shape[0]} encodes to width {width}, "
                f"which no compiled bucket accepts (design {design!r} "
                f"envelope takes width {cfg.p})",
            )
        if not np.isfinite(x).all():
            self._reject(
                "non-finite", f"series for {design!r} has non-finite samples"
            )
        deadline = (
            deadline_s if deadline_s is not None else self.default_deadline_s
        )
        if deadline is not None:
            est = self._wait_estimate_s(bucket)
            if est > deadline:
                self._reject(
                    "deadline",
                    f"predicted wait {est:.4f}s exceeds deadline budget "
                    f"{deadline:.4f}s",
                    retry_after_s=est,
                )
        return bucket, lane, cfg, x, deadline

    def flush(self, design: Optional[str] = None) -> None:
        """Execute partial batches now (all buckets, or ``design``'s)."""
        buckets = (
            self._buckets if design is None else [self._route[design][0]]
        )
        with obs.span("serve.flush"):
            for b in buckets:
                while b.queue:
                    self._execute(b)

    # --------------------------------------------------------- execution
    def _silent_batch(self, bucket: _Bucket) -> np.ndarray:
        p_env, _, t_window = bucket.envelope
        return np.full(
            (self.batch_size, len(bucket.names), p_env), t_window, np.int32
        )

    def _batch_xs(self, bucket: _Bucket, reqs: list[_Request]) -> np.ndarray:
        """Assemble [B, Db, p_env] host-side: each request's volley in its
        design's lane, every other lane silent, partial batches padded to
        the compiled batch size through the ragged-batch seam."""
        p_env, _, t_window = bucket.envelope
        xs = np.full(
            (len(reqs), len(bucket.names), p_env), t_window, np.int32
        )
        for n, r in enumerate(reqs):
            xs[n, r.lane, : r.enc.shape[0]] = r.enc
        return fused_column.pad_stream_silent(xs, self.batch_size, t_window)

    def _assign(self, bucket: _Bucket, xs_np: np.ndarray) -> np.ndarray:
        ids = backend_lib.assign_padded(
            bucket.w, jnp.asarray(xs_np),
            bucket.thresholds, bucket.t_maxes, bucket.q_actives,
            t_window=bucket.envelope[2], wta_k=self._statics["wta_k"],
            response=self._statics["response"],
            lowering=bucket.asg_lowering, w_max=self._statics["w_max"],
        )
        ids = np.asarray(ids)  # [Db, B]
        obs.count(
            ASSIGN_REFERENCE if bucket.asg_lowering == "reference"
            else ASSIGN_MOSAIC
        )
        return ids

    def _shed_expired(self, reqs: list[_Request]) -> list[_Request]:
        """Drop deadline-expired requests from a popped batch BEFORE any
        JAX work — their budget is already blown, serving them would only
        delay the live ones."""
        now = time.perf_counter()
        live = []
        for r in reqs:
            waited = now - r.t_submit
            if r.deadline is not None and waited > r.deadline:
                self._shed += 1
                r.pending.outcome = ServeShed(
                    r.pending.id, r.pending.design, "deadline", waited
                )
            else:
                live.append(r)
        return live

    def _execute(self, bucket: _Bucket) -> None:
        reqs = bucket.queue[: self.batch_size]
        del bucket.queue[: self.batch_size]
        if not reqs:
            return
        reqs = self._shed_expired(reqs)
        if not reqs:
            return
        with obs.span("serve.execute", bucket=bucket.index,
                      batch=self._batches, live=len(reqs)):
            obs.count("serve.rows_live", len(reqs))
            obs.count("serve.rows_slots", self.batch_size)
            self.monitor.start("assign")
            t0 = time.perf_counter()
            try:
                with obs.span("serve.batch_xs"):
                    xs = self._batch_xs(bucket, reqs)
                with obs.span("serve.assign"):
                    ids = self._assign(bucket, xs)
            except Exception as e:
                self.monitor.stop()
                warnings.warn(
                    f"assign of bucket {bucket.index} "
                    f"({bucket.asg_lowering}) failed, isolating its "
                    f"{len(reqs)} request(s): {e!r}",
                    RuntimeWarning,
                )
                self._isolate(bucket, reqs)
                return
            self.monitor.stop()
            done = time.perf_counter()
            dt = done - t0
            self._batch_ewma = (
                dt if self._batch_ewma is None
                else 0.8 * self._batch_ewma + 0.2 * dt
            )
            self._batches += 1
            with obs.span("serve.complete"):
                for n, r in enumerate(reqs):
                    self._complete(
                        bucket, r,
                        ServeResult(
                            r.pending.id, r.pending.design,
                            int(ids[r.lane, n]), done - r.t_submit,
                        ),
                    )
            self._maybe_refit(bucket)

    def _isolate(self, bucket: _Bucket, reqs: list[_Request]) -> None:
        """Quarantine: re-run each request of a failed batch alone against
        the SAME executable (one live row, rest silent) — assignment is
        per-volley independent, so survivors' answers are bit-identical
        to the batched run; only the poisoned request fails."""
        self._isolations += 1
        for r in reqs:
            self.monitor.start("assign")
            try:
                ids = self._assign(bucket, self._batch_xs(bucket, [r]))
            except Exception as e:
                self.monitor.stop()
                self._failed += 1
                r.pending.outcome = ServeFailure(
                    r.pending.id, r.pending.design, "assign", repr(e)
                )
                continue
            self.monitor.stop()
            self._batches += 1
            self._complete(
                bucket, r,
                ServeResult(
                    r.pending.id, r.pending.design,
                    int(ids[r.lane, 0]), time.perf_counter() - r.t_submit,
                ),
            )
        self._maybe_refit(bucket)

    def _complete(
        self, bucket: _Bucket, r: _Request, result: ServeResult
    ) -> None:
        r.pending.outcome = result
        self._served += 1
        bucket.served_since_refit += 1
        if self.refit_every > 0:
            buf = bucket.buffers[r.lane]
            buf.append(r.enc)
            if len(buf) > self.refit_window:
                del buf[: len(buf) - self.refit_window]

    # ------------------------------------------------------------ re-fit
    def _refit_xs(self, bucket: _Bucket) -> np.ndarray:
        """[R, Db, p_env] re-fit window: each design's buffered volleys in
        arrival order, ragged tails silent (exact no-ops above threshold
        0, so training on the padded window == training on the buffered
        volleys alone)."""
        p_env, _, t_window = bucket.envelope
        xs = np.full(
            (self.refit_window, len(bucket.names), p_env), t_window, np.int32
        )
        for lane, buf in enumerate(bucket.buffers):
            for k, enc in enumerate(buf):
                xs[k, lane, : enc.shape[0]] = enc
        return xs

    def _fit_window(self, bucket: _Bucket, xs_np: np.ndarray,
                    lowering: str, stream=None) -> jnp.ndarray:
        """One fused online-STDP pass over a host-side window, on a COPY
        of the live block — ``fit_padded`` donates its weight operand, and
        a failed or discarded attempt must never destroy the last-good
        weights (donation is a memory optimization; the copy is
        value-identical, so commit-on-success keeps the resume contract
        bit-exact).  ``stream`` is a stochastic window's ``(keys [Db, 2],
        first volley index)``; the 'cycle' rung re-runs it design by
        design on the solver, drawing the same bits."""
        if lowering == "cycle":
            return self._solver_window(bucket, xs_np, *stream)
        keys, v0 = (None, 0) if stream is None else stream
        w_new = backend_lib.fit_padded(
            jnp.array(bucket.w, copy=True), jnp.asarray(xs_np),
            bucket.thresholds, bucket.t_maxes, bucket.q_actives,
            t_window=bucket.envelope[2],
            epochs=self.refit_epochs, lowering=lowering,
            keys=keys, v0=v0, **self._statics,
        )
        return jax.block_until_ready(w_new)

    def _solver_window(self, bucket: _Bucket, xs_np: np.ndarray, keys,
                       v0: int) -> jnp.ndarray:
        """The 'cycle' rung of a stochastic window: each design's own
        (p, q) block trained on its lane of the window, from the same
        stream index; the envelope padding is carried over."""
        w = np.array(bucket.w)
        for lane, c in enumerate(bucket.cfgs):
            w[lane, : c.p, : c.q] = np.asarray(backend_lib.solver_fit_stream(
                w[lane, : c.p, : c.q], xs_np[:, lane, : c.p], c,
                epochs=self.refit_epochs, stream_key=keys[lane], v0=v0,
            ))
        return jnp.asarray(w)

    def _ladder(self, bucket: _Bucket) -> tuple[str, ...]:
        return backend_lib.lowering_ladder(
            bucket.fit_lowering, cycle_exact=bucket.cycle_exact
        )

    def _stream_advance(self) -> int:
        """Stream indices one committed re-fit draws: every epoch over the
        whole padded window."""
        return self.refit_epochs * self.refit_window

    def _attempt_window(self, bucket: _Bucket, xs_np: np.ndarray, *,
                        ladder, stream=None, label: str = "refit",
                        enforce_budget: bool = True):
        """Try one re-fit window down ``ladder``; a rung fails on raise,
        non-finite weights, or (with the watchdog budget enforced) a wall
        time over ``refit_budget_s``.  Every rung draws from the same
        ``stream`` (stochastic STDP).  Returns ``(w_new, lowering,
        errors)`` — ``w_new`` is ``None`` when every rung failed."""
        errors: list[str] = []

        def failed(low: str, why: str) -> None:
            errors.append(f"{low}: {why}")
            self._refit_retries += 1
            backend_lib.warn_step_down(
                f"{label} of bucket {bucket.index}", low, why
            )

        for low in ladder:
            self.monitor.start(label)
            t0 = time.perf_counter()
            try:
                with obs.span("serve.fit", lowering=low,
                              rule=self._rule):
                    w_new = self._fit_window(bucket, xs_np, low, stream)
            except Exception as e:
                self.monitor.stop()
                failed(low, repr(e))
                continue
            self.monitor.stop()
            dt = time.perf_counter() - t0
            if (
                enforce_budget
                and self.refit_budget_s is not None
                and dt > self.refit_budget_s
            ):
                self._refit_stalls += 1
                failed(
                    low,
                    f"stalled ({dt:.3f}s > refit_budget_s="
                    f"{self.refit_budget_s:.3f}s) — result discarded",
                )
                continue
            with obs.span("serve.refit_check"):
                finite = bool(jnp.isfinite(w_new).all())
            if not finite:
                failed(low, "non-finite weights (poisoned re-fit)")
                continue
            return w_new, low, errors
        return None, None, errors

    def _commit_weights(self, bucket: _Bucket, w_new) -> None:
        bucket.w = jnp.asarray(w_new)
        # off the integer grid the assignment lowering stays 'reference'
        # on every host; re-checking the whole block after each commit
        # keeps the kernel available on TPU should the weights land back
        # on the grid
        bucket.asg_lowering = backend_lib.assign_lowering(
            self._statics["response"], bucket.w
        )

    def _refit(self, bucket: _Bucket, warm: bool = False) -> None:
        with obs.span("serve.refit", bucket=bucket.index):
            with obs.span("serve.refit_xs"):
                xs = self._refit_xs(bucket)
            stream = None
            if self.stochastic:
                stream = (bucket.keys, bucket.v_base)
            if warm:
                # warmup's all-silent window: single rung, no budget (first
                # dispatch may still be cold), no WAL, no counters, and no
                # stream indices drawn (the base stays)
                w_new, _, _ = self._attempt_window(
                    bucket, xs, ladder=(bucket.fit_lowering,),
                    stream=stream, enforce_budget=False,
                )
                if w_new is not None:
                    with obs.span("serve.commit"):
                        self._commit_weights(bucket, w_new)
            else:
                w_new, _low, errors = self._attempt_window(
                    bucket, xs, ladder=self._ladder(bucket), stream=stream,
                )
                if w_new is None:
                    # degraded mode: keep serving from last-good weights;
                    # retry after an exponentially growing number of windows
                    self._refit_failures += 1
                    bucket.failed_refits += 1
                    bucket.cooldown = backend_lib.refit_backoff(
                        bucket.failed_refits
                    )
                    bucket.degraded = True
                    bucket.last_refit_errors = errors
                else:
                    with obs.span("serve.commit"):
                        self._commit_weights(bucket, w_new)
                    obs.count(
                        REFIT_DESIGN_VOLLEYS,
                        self.refit_epochs
                        * sum(len(buf) for buf in bucket.buffers),
                    )
                    if stream is not None:
                        bucket.v_base += self._stream_advance()
                    bucket.last_fit_lowering = _low
                    self._refits += 1
                    self._refit_seq += 1
                    if bucket.degraded:
                        bucket.degraded = False
                        bucket.failed_refits = 0
                        bucket.cooldown = 0
                        bucket.last_refit_errors = []
                        self._recoveries += 1
                    if self._store is not None:
                        with obs.span("serve.wal"):
                            self._store.log_refit(
                                self._refit_seq, bucket.index,
                                self.refit_epochs, _low, xs,
                                stream=None if stream is None else {
                                    "keys": np.asarray(stream[0]).tolist(),
                                    "v_base": stream[1],
                                },
                            )
                            if self._refit_seq % self.snapshot_every == 0:
                                self._snapshot()
            for buf in bucket.buffers:
                buf.clear()
            bucket.served_since_refit = 0

    def _maybe_refit(self, bucket: _Bucket) -> None:
        if (
            self.refit_every <= 0
            or bucket.served_since_refit < self.refit_every
            or not any(bucket.buffers)
        ):
            return
        if bucket.cooldown > 0:
            # degraded backoff: sit this window out (buffers keep rolling,
            # capped at refit_window) and wait a full window before the
            # next decision
            bucket.cooldown -= 1
            bucket.served_since_refit = 0
            return
        self._refit(bucket)
