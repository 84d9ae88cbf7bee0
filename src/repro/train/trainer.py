"""Training loop with the Mimose planner on the critical path (paper §4.1).

The trainer is the execution half of the *compile-once bucketed engine*:

  1. Each incoming batch is padded up to the planner's quantum
     (``repro.data.pipeline.pad_batch``) so batch geometry is always
     drawn from the small fixed bucket set; the true ``lengths`` stay in
     the batch dict until the loss weights are materialised, so masking
     is exact and padded positions contribute nothing.
  2. ``planner.plan`` maps the bucket to a typed action plan
     (``repro.actions.Action``: KEEP / REMAT / OFFLOAD-to-host) — cached
     plans are O(1); new buckets cost <1 ms (estimator + scheduler) or
     one deduplicated abstract collection during sheltered execution.
  3. The plan cache and the jit-step cache share one key: the planner's
     ``bucket_key`` (quantised input size).  Because padding collapses
     every raw shape in a bucket onto the bucket's canonical shape, a
     repeated bucket never recompiles *or* replans, and total XLA
     compiles are bounded by #buckets, not #distinct raw shapes.  Both
     caches are bounded LRUs (``max_cached_steps`` here, ``max_plans``
     on the planner) with eviction counters, so a long-tailed bucket
     distribution cannot pin a compiled executable per rare bucket.
  4. ``prewarm`` AOT-compiles (``jit.lower(...).compile()``) the top-k
     buckets off the critical path before step 0, so the first epoch
     never stalls on mid-training compilation.
  5. When the plan carries a gradient-accumulation split
     (``Plan.microbatch > 1``, chosen by the adaptive-microbatching
     planner), the step executes as a ``lax.scan`` over ``k``
     microbatches (``repro.train.accumulate``) with token-weighted
     accumulation, so loss/grads match the full-batch step exactly.
     The jit-step cache key includes ``k``; ``StepStats.microbatches``
     and ``summary()['mean_microbatches']`` report where it kicked in.
  6. ``step`` dispatches a step before it reads the loss of the one
     before it, so the host's work around a step overlaps the chip's
     work on the previous one.  The effective token count comes from
     the host batch, so that loss is the only value a warm step reads
     back from the device.

Sharding: pass ``mesh`` to build and run every step under that Mesh
context (required for ``with_sharding_constraint`` in the model).  The
jit-step cache key embeds the planner's mesh signature, so executables
compiled for one mesh shape are never replayed under another — the
execution-side mirror of the planner's (bucket, mesh) plan-cache key.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections.abc import MutableMapping
from typing import Any, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import LRUCache
from repro.core.planner import PlannerBase
from repro.data.pipeline import pad_batch
from repro.models.lm import LM
from repro.obs import LabelView, StatsView, Telemetry, TRACK_STEP
from repro.optim.adamw import AdamW, AdamWState
from repro.train.accumulate import accumulated_grads, build_accumulated_step
from repro.train.transfer import TransferLane


def effective_tokens(batch) -> int:
    """The effective tokens of a padded host batch as ``lm.loss`` counts
    them (its ``metrics["tokens"]``): ``max(sum(weights), 1)``, every
    label position when the batch has no weights."""
    w = batch.get("weights")
    if w is None:
        return max(int(np.prod(np.shape(batch.get("labels",
                                                  batch["tokens"])))), 1)
    return int(max(float(np.sum(np.asarray(w), dtype=np.float64)), 1.0))


class DeferredStepError(RuntimeError):
    """A step failed on the device, seen only when its loss was read one
    step late; the message names that step.  It is not retried: the
    step's donated inputs are gone."""


@dataclasses.dataclass
class StepStats:
    # filled when the step's loss is read: by the next ``Trainer.step``,
    # ``Trainer.drain`` or the first read of ``loss`` (see _ReadsPending)
    loss: float
    step_time_s: float
    plan_time_s: float
    compile: bool
    remat_units: int
    tokens: int                # effective (unpadded) tokens in the step
    bucket: int = 0
    padded_tokens: int = 0     # bucket-shape tokens actually computed over
    offload_units: int = 0     # units whose residuals went to host memory
    microbatches: int = 1      # gradient-accumulation split of the step
    opt_offload_units: int = 0  # units whose optimizer moments were parked
    # True when the plan carried OFFLOAD actions but this runtime/mesh
    # cannot execute real host offload (lm.offload_exec == False): the
    # step ran them as plain remat — the silent SPMD degradation, made
    # visible (see launch/report.engine_report)
    offload_degraded: bool = False
    # measured wall time this step spent BLOCKED on host<->device
    # moment traffic (TransferLane accounting), and what the simulator's
    # pricing predicts for the same bytes — the pair the bench gate
    # holds to a tolerance band
    exposed_transfer_s: float = 0.0
    sim_transfer_s: float = 0.0
    # the planner's predicted per-device peak under this step's plan:
    # fixed bytes, the output head's, and the activation bytes the plan
    # keeps on the device
    planned_peak_bytes: float = 0.0
    # the planner's term for the blocked output head and loss at this
    # step's bucket, and the head's number of blocks of tokens
    head_bytes: float = 0.0
    head_blocks: int = 0


class _ReadsPending:
    """``StepStats.loss``: while the step's loss is still on the device,
    reading it reads the loss first (``_settle``, set by the trainer)."""

    def __get__(self, obj, owner=None):
        if obj is None:
            return self
        settle = obj.__dict__.get("_settle")
        if settle is not None:
            settle()
        return obj.__dict__["loss"]

    def __set__(self, obj, value):
        obj.__dict__["loss"] = value


# installed after the dataclass is built, so ``loss`` stays its first
# positional field and dataclasses.asdict / repr read it through this
StepStats.loss = _ReadsPending()


class _Pending(NamedTuple):
    """A dispatched step whose loss has not been read yet."""
    step: int
    loss: Any                  # 0-d device array
    stats: StepStats
    t_start: float             # perf_counter at its dispatch
    event: Optional[dict]      # its ``train_step`` event, less the loss


class Trainer:
    def __init__(self, lm: LM, planner: PlannerBase,
                 optimizer: Optional[AdamW] = None,
                 remat_policy=None,
                 bucket_pad: bool = True,
                 mesh=None,
                 max_cached_steps: int = 64,
                 watchdog=None,
                 snapshots=None,
                 telemetry: Optional[Telemetry] = None):
        self.lm = lm
        self.planner = planner
        # ONE registry per run: the trainer's telemetry is authoritative
        # and the planner / watchdog / snapshot manager re-home their
        # metrics into it, so overlapping counters (oom_events,
        # escalations) become a single shared metric instead of
        # parallel bookkeeping (repro.obs)
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry.disabled()
        planner.bind_telemetry(self.telemetry)
        self.optimizer = optimizer or AdamW()
        self.remat_policy = remat_policy
        self.bucket_pad = bucket_pad
        self.mesh = mesh                  # jax.sharding.Mesh or None
        # elastic resilience (repro.train.resilience): the OOM watchdog
        # wraps step execution in a bounded retry/escalate loop, and the
        # snapshot manager periodically persists full training state
        self.watchdog = watchdog          # resilience.OOMWatchdog or None
        self.snapshots = snapshots        # resilience.SnapshotManager or None
        self.global_step = 0              # across restarts (set on resume)
        self.data_cursor = 0              # batches consumed from the stream
        self.restores = 0                 # snapshots restored into this run
        # real offload execution: one dedicated transfer lane (lazy —
        # only plans with OFFLOAD_OPT units ever create it) moves
        # optimizer moments device<->host with double buffering; the
        # parked-unit set is the execution-side record of which units'
        # moments currently live on the host
        self.transfer_lane: Optional[TransferLane] = None
        self._parked: set = set()
        self._degraded_buckets: set = set()
        # bounded LRU: a long-tailed bucket distribution must not pin a
        # compiled executable per rare bucket forever
        self._step_cache = LRUCache(max_cached_steps)
        self.history: list[StepStats] = []
        # the step dispatched last, its loss not read yet, and the
        # perf_counter at which the loss before it was read
        self._pending: Optional[_Pending] = None
        self._t_read = 0.0
        reg = self.telemetry.metrics
        # per bucket: padded vs effective tokens (where the padding
        # waste went — launch/report.engine_report) and the largest
        # gradient-accumulation split the planner picked
        self._m_padded_tokens = reg.counter(
            "train_bucket_padded_tokens",
            "bucket-shape tokens actually computed over")
        self._m_eff_tokens = reg.counter(
            "train_bucket_tokens", "effective (unpadded) tokens")
        self._g_bucket_k = reg.gauge(
            "train_bucket_microbatch",
            "largest gradient-accumulation split seen per bucket")
        self._g_head_bytes = reg.gauge(
            "train_head_bytes",
            "the planner's bytes for the blocked output head per bucket")
        self._g_head_blocks = reg.gauge(
            "train_head_blocks",
            "blocks of tokens the output head and loss run in per bucket")
        self._h_step_s = reg.histogram(
            "train_step_time_s", "wall time per executed train step")
        self._m_deferred = reg.counter(
            "train_loss_reads_deferred",
            "steps whose loss was read after the next step's dispatch")
        self.cache_stats = StatsView(
            reg,
            scalars={"compiles": "train_jit_compiles",
                     "prewarm_compiles": "train_jit_prewarm_compiles",
                     "jit_hits": "train_jit_hits",
                     "evictions": "train_jit_evictions"},
            labeled={"bucket_steps": ("train_bucket_steps", "bucket")},
            composite={
                "bucket_tokens": self._bucket_tokens_view,
                "bucket_microbatch":
                    lambda: LabelView(self._g_bucket_k, "bucket")})

    # watchdog / snapshots are properties so a post-construction
    # assignment (``tr.watchdog = OOMWatchdog(...)``) still re-homes the
    # component's metrics into the trainer's registry — the shared
    # oom_events / escalations counters only exist when both sides are
    # bound to the same registry
    @property
    def watchdog(self):
        return self._watchdog

    @watchdog.setter
    def watchdog(self, wd) -> None:
        if wd is not None and hasattr(wd, "bind_telemetry"):
            wd.bind_telemetry(self.telemetry)
        self._watchdog = wd

    @property
    def snapshots(self):
        return self._snapshots

    @snapshots.setter
    def snapshots(self, sm) -> None:
        if sm is not None and hasattr(sm, "bind_telemetry"):
            sm.bind_telemetry(self.telemetry)
        self._snapshots = sm

    def _bucket_tokens_view(self) -> dict:
        """``{bucket: [padded_tokens, effective_tokens]}`` materialised
        from the two per-bucket token counters."""
        padded = LabelView(self._m_padded_tokens, "bucket")
        eff = LabelView(self._m_eff_tokens, "bucket")
        return {b: [padded.get(b, 0), eff.get(b, 0)]
                for b in set(padded) | set(eff)}

    # ------------------------------------------------------------------
    def _batch_key(self, batch) -> tuple:
        # dtypes matter, not just shapes: prewarmed entries are AOT
        # Compiled executables fixed to the exact avals they were lowered
        # with — a same-shape/different-dtype batch must miss the cache
        # and compile, not crash inside a Compiled call.  ``lengths`` is
        # excluded: _prepare always materialises it as (B,) int32, whose
        # aval is implied by the tokens shape already in the key — its
        # *values* are runtime operands of the length-aware kernels, so
        # raggedness never forces a recompile.
        return tuple(sorted((k, tuple(np.shape(v)),
                             str(getattr(v, "dtype", "")))
                            for k, v in batch.items() if k != "lengths"))

    def _pad(self, batch) -> dict:
        """Bucket-pad one batch on the host.

        The true ``lengths`` stay in the batch (defaulted to the full
        sequence when absent) so the model can thread them into the
        length-aware kernels — padded positions are masked out of
        attention/SSD and skipped blockwise, not just zero-weighted in
        the loss."""
        if self.bucket_pad:
            batch = pad_batch(batch, getattr(self.planner, "quantum", 1))
        B, S = np.shape(batch["tokens"])
        if "lengths" not in batch:
            batch = dict(batch)
            batch["lengths"] = np.full((B,), S, np.int32)
        return batch

    @staticmethod
    def _put(batch) -> dict:
        return {k: jnp.asarray(np.asarray(v, np.int32) if k == "lengths"
                               else v)
                for k, v in batch.items()}

    def _prepare(self, batch) -> dict:
        """Bucket-pad and device-put one batch."""
        return self._put(self._pad(batch))

    def _build_step(self, mask, microbatch: int = 1):
        opt = self.optimizer
        lm = self.lm
        policy = self.remat_policy
        opt_units = tuple(i for i, m in enumerate(mask) if int(m) == 3)
        if opt_units and lm.cfg.remat_mode != "scan":
            # OFFLOAD_OPT (ZeRO-Offload style): the step splits into a
            # grad phase and an update phase, because the parked units'
            # moments must be OFF the device exactly while activations
            # peak (forward/backward) and on it only for opt.update.
            # The trainer runs the choreography (_run_opt_split): grads
            # dispatch async, the TransferLane uploads parked moments
            # behind the backward pass, update runs, fresh moments
            # stream back out.
            if microbatch > 1:
                def grad_fn(p, b):
                    return accumulated_grads(lm, p, b, microbatch,
                                             actions=mask,
                                             remat_policy=policy)
            else:
                def grad_fn(p, b):
                    def loss_fn(pp):
                        return lm.loss(pp, b, remat_mask=mask,
                                       remat_policy=policy)
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(p)
                    return loss, metrics, grads

            # donate grads (aliases new_params) and the moment state;
            # params must NOT be donated too — outputs consume only two
            # params-worth of buffers, a third donated set would just
            # warn as unusable
            update_fn = jax.jit(
                lambda g, s, p: opt.update(g, s, p),
                donate_argnums=(0, 1))
            return ("opt_split", jax.jit(grad_fn), update_fn, opt_units)
        if microbatch > 1:
            # k-way gradient accumulation: one lax.scan over the split
            # batch, token-weighted so loss/grads match the full-batch
            # step exactly (repro.train.accumulate)
            return build_accumulated_step(lm, opt, mask, microbatch,
                                          remat_policy=policy)

        def train_step(params, opt_state, batch):
            def loss_fn(p):
                loss, metrics = lm.loss(p, batch, remat_mask=mask,
                                        remat_policy=policy)
                return loss, metrics
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with jax.named_scope("optimizer"):
                new_params, new_opt = opt.update(grads, opt_state, params)
            return new_params, new_opt, loss, metrics

        return jax.jit(train_step, donate_argnums=(0, 1))

    def _step_key(self, mask, batch, microbatch: int = 1) -> tuple:
        # the bucket id is fully determined by the padded shapes already in
        # the batch signature (bucket = quantised element count), so the
        # jit cache keys on (shapes, action plan, microbatch split, mesh
        # signature) and aligns with the plan cache (keyed on (bucket id,
        # mesh signature, max_microbatches)) through the shared
        # bucket_length rounding + planner.mesh_sig.  ``mask`` is the
        # planner's typed action tuple (or a legacy bool tuple) — two
        # plans that remat the same units but offload or split
        # differently must compile separately.
        return (self._batch_key(batch), tuple(int(m) for m in mask),
                int(microbatch), self.planner.mesh_sig())

    def _mesh_ctx(self):
        """Mesh context for compile + execute (no-op without a mesh)."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _get_step_fn(self, mask, batch, microbatch: int = 1, bucket=None):
        key = self._step_key(mask, batch, microbatch)
        fn = self._step_cache.get(key)
        if fn is None:
            tel = self.telemetry
            with tel.tracer.span("build_step", TRACK_STEP,
                                 args={"bucket": bucket}
                                 if tel.trace_on else None):
                fn = self._build_step(mask, microbatch)
            self._step_cache[key] = fn
            self.cache_stats["compiles"] += 1
            self.cache_stats["evictions"] = self._step_cache.evictions
            return fn, True
        self.cache_stats["jit_hits"] += 1
        return fn, False

    # -- optimizer-moment parking (OFFLOAD_OPT execution) ---------------
    def _lane(self) -> TransferLane:
        if self.transfer_lane is None:
            self.transfer_lane = TransferLane(
                mesh_sig=self.planner.mesh_sig(),
                telemetry=self.telemetry)
        return self.transfer_lane

    def _moment_get(self, tree, u: int):
        """The moment subtree of plan unit ``u`` (unrolled mode: enc
        units first, then decoder blocks — mirrors LM.plan_units)."""
        enc = self.lm._num_enc_units()
        if u < enc:
            return tree["encoder"]["blocks"][u]
        return tree["blocks"][u - enc]

    def _moment_set(self, tree, u: int, val):
        enc = self.lm._num_enc_units()
        t = dict(tree)
        if u < enc:
            te = dict(t["encoder"])
            bl = list(te["blocks"])
            bl[u] = val
            te["blocks"] = bl
            t["encoder"] = te
        else:
            bl = list(t["blocks"])
            bl[u - enc] = val
            t["blocks"] = bl
        return t

    def _park_moments(self, opt_state: AdamWState, opt_units) -> AdamWState:
        """Stream the fp32 AdamW m/v of every OFFLOAD_OPT unit to host
        memory on the transfer lane and splice the host buffers into the
        state tree — those bytes are genuinely off the device until the
        next update phase.  All copies are started before any is waited
        on, so the lane double-buffers across units."""
        if not opt_units:
            self._parked = set()
            return opt_state
        lane = self._lane()
        m, v = opt_state.m, opt_state.v
        pending = []
        for u in opt_units:
            for which, tree in (("m", m), ("v", v)):
                leaves, tdef = jax.tree_util.tree_flatten(
                    self._moment_get(tree, u))
                pending.append((u, which, tdef,
                                [lane.offload(x) for x in leaves]))
        for u, which, tdef, hs in pending:
            sub = jax.tree_util.tree_unflatten(
                tdef, [lane.host_value(h) for h in hs])
            if which == "m":
                m = self._moment_set(m, u, sub)
            else:
                v = self._moment_set(v, u, sub)
        self._parked = set(opt_units)
        return opt_state._replace(m=m, v=v)

    def _unpark_moments(self, opt_state: AdamWState) -> AdamWState:
        """Bring every parked moment subtree back to the device (called
        with the backward pass already dispatched, so the lane's H2D
        copies ride behind device compute)."""
        if not self._parked:
            return opt_state
        lane = self._lane()
        m, v = opt_state.m, opt_state.v
        pending = []
        for u in sorted(self._parked):
            for which, tree in (("m", m), ("v", v)):
                leaves, tdef = jax.tree_util.tree_flatten(
                    self._moment_get(tree, u))
                pending.append((u, which, tdef,
                                [lane.upload(x) for x in leaves]))
        for u, which, tdef, hs in pending:
            sub = jax.tree_util.tree_unflatten(
                tdef, [lane.fetch(h) for h in hs])
            if which == "m":
                m = self._moment_set(m, u, sub)
            else:
                v = self._moment_set(v, u, sub)
        self._parked = set()
        return opt_state._replace(m=m, v=v)

    def _run_opt_split(self, fn, params, opt_state, batch):
        """Execute one OFFLOAD_OPT step: grads dispatch asynchronously,
        parked moments stream home behind the backward pass, the update
        runs with everything on device, and the new plan's moments
        stream back out."""
        _tag, grad_fn, update_fn, opt_units = fn
        loss, metrics, grads = grad_fn(params, batch)
        opt_state = self._unpark_moments(opt_state)
        new_params, new_opt = update_fn(grads, opt_state, params)
        new_opt = self._park_moments(new_opt, opt_units)
        return new_params, new_opt, loss, metrics

    # ------------------------------------------------------------------
    def prewarm(self, params, opt_state: AdamWState,
                seq_lens: Iterable[int], batch_size: int,
                extra=None) -> int:
        """AOT-compile the train step for the given bucket seq-lens off
        the critical path (``jit.lower(...).compile()`` — no step is
        executed, params are untouched).  Plans for those buckets are
        computed and cached along the way, so the first real batch of a
        prewarmed bucket is a pure cache hit on both caches.

        ``extra`` maps additional batch keys to ``fn(batch_size, S) ->
        array`` builders (the ``make_batches`` convention) — required for
        families whose batches carry more than tokens/labels/weights
        (encoder ``frames``, VLM ``vision_embeds``).  Returns the number
        of executables compiled."""
        n = 0
        for S in seq_lens:
            raw = {
                "tokens": np.zeros((batch_size, int(S)), np.int32),
                "labels": np.zeros((batch_size, int(S)), np.int32),
                "weights": np.ones((batch_size, int(S)), np.float32),
            }
            if extra:
                raw.update({k: v(batch_size, int(S))
                            for k, v in extra.items()})
            batch = self._prepare(raw)
            mask, _info = self.planner.plan(params, batch)
            k = max(int(getattr(_info.plan, "microbatch", 1)), 1)
            key = self._step_key(mask, batch, k)
            if key in self._step_cache:
                continue
            fn = self._build_step(mask, k)
            with self._mesh_ctx():
                if isinstance(fn, tuple):
                    # opt-split step: AOT-compile the grad phase (the
                    # memory-critical one); the small update phase jits
                    # on first use
                    tag, gf, uf, units = fn
                    gf = gf.lower(params, batch).compile()
                    self._step_cache[key] = (tag, gf, uf, units)
                else:
                    self._step_cache[key] = fn.lower(params, opt_state,
                                                     batch).compile()
            self.cache_stats["prewarm_compiles"] += 1
            self.cache_stats["evictions"] = self._step_cache.evictions
            n += 1
        return n

    # ------------------------------------------------------------------
    def step(self, params, opt_state: AdamWState, batch) -> tuple:
        """One training step, its loss read one step late.

        The step is dispatched first; only then does the host wait for
        the chip, on the loss of the step before it, so the chip has
        this step queued through that wait and through the host work
        that follows it.  Returns the new params and optimizer state and
        this step's loss as an unread 0-d device array.
        ``history[-1]`` describes this step on return; its ``loss`` and
        ``step_time_s`` fill when the loss is read: by the next step,
        by :meth:`drain`, or on the first read of ``history[-1].loss``.

        Every host stretch is a span on ``TRACK_STEP`` inside the outer
        ``step``: ``prepare``, ``plan``, ``build_step`` (a step-cache
        miss), ``execute`` (its ``dispatch``, then the ``sync`` that
        reads the previous step's loss) and ``record`` (the bookkeeping
        after the dispatch), so a profiler trace names the device time
        the host leaves idle."""
        tracer = self.telemetry.tracer
        with tracer.step_span("step", self.global_step):
            with tracer.span("prepare", TRACK_STEP):
                batch = self._pad(batch)
                tokens = effective_tokens(batch)
                batch = self._put(batch)
            params, opt_state, loss, ctx = self._execute(
                params, opt_state, batch)
            with tracer.span("record", TRACK_STEP):
                self._record(params, opt_state, loss, tokens, batch, ctx)
        return params, opt_state, loss

    def drain(self) -> None:
        """Read the loss of the last dispatched step and finish its
        bookkeeping: ``StepStats.loss`` and ``step_time_s``, the
        ``train_step`` event.  Raises :class:`DeferredStepError` if that
        step failed on the device."""
        self._sync(deferred=False)

    def _sync(self, deferred: bool) -> None:
        """Read the pending step's loss in a ``sync`` span (nothing when
        no step is pending).  ``deferred``: the next step is already
        dispatched (``train_loss_reads_deferred``)."""
        p = self._pending
        if p is None:
            return
        self._pending = None
        st = p.stats
        del st._settle
        tel = self.telemetry
        with tel.tracer.span("sync", TRACK_STEP,
                             args={"step": p.step} if tel.trace_on
                             else None):
            try:
                loss = float(p.loss)
            except Exception as e:
                raise DeferredStepError(
                    f"step {p.step} failed on the device: {e}") from e
        t_read = time.perf_counter()
        # from the previous loss read, so summary()'s rate stays paced
        # by the chip; from its own dispatch when the chip had nothing
        # before it (the first step, a compile, a pause in the caller)
        st.step_time_s = t_read - (p.t_start if st.compile
                                   else max(p.t_start, self._t_read))
        st.loss = loss
        self._t_read = t_read
        self._h_step_s.observe(st.step_time_s)
        if deferred:
            self._m_deferred.inc()
        if p.event is not None:
            tel.events.emit("train_step", loss=loss,
                            step_time_s=st.step_time_s, **p.event)

    def _execute(self, params, opt_state: AdamWState, batch) -> tuple:
        """Plan, build or look up the step and dispatch it under the OOM
        watchdog's retry loop, then read the previous step's loss.
        ``ctx`` carries what :meth:`_record` books: (plan info, bucket,
        microbatch split, compiled now, dispatch start, plan seconds)."""
        tel = self.telemetry
        tracer = tel.tracer
        t0 = time.perf_counter()
        with tracer.span("plan", TRACK_STEP):
            mask, info = self.planner.plan(params, batch)
        t_plan = time.perf_counter() - t0

        bucket = self.planner.bucket_key(batch)
        wd = self.watchdog
        attempt = 0
        while True:
            k = max(int(getattr(info.plan, "microbatch", 1)), 1)
            fn, is_new = self._get_step_fn(mask, batch, k, bucket)
            if is_new:
                # a compile far outlasts the step in flight: read that
                # step first, so its time holds none of the compile
                self._sync(deferred=False)
            if self.transfer_lane is not None:
                self.transfer_lane.reset_stats()
            t1 = time.perf_counter()
            try:
                if wd is not None:
                    # injected faults fire BEFORE the jit call so no
                    # donated buffer is consumed by a simulated failure
                    wd.maybe_inject(step=self.global_step, bucket=bucket)
                with self._mesh_ctx(), tracer.span("execute", TRACK_STEP):
                    with tracer.span("dispatch", TRACK_STEP):
                        if isinstance(fn, tuple) and fn[0] == "opt_split":
                            params, opt_state, loss, _ = \
                                self._run_opt_split(fn, params, opt_state,
                                                    batch)
                        else:
                            params, opt_state, loss, _ = fn(
                                params, opt_state, batch)
                    # this step is queued: now wait on the loss of the
                    # one before it.  A failure there is that step's,
                    # raised as DeferredStepError and never retried
                    self._sync(deferred=True)
            except Exception as e:
                if wd is None or isinstance(e, DeferredStepError) \
                        or not wd.is_oom(e):
                    raise
                # the plan predicted this bucket fits; reality disagreed —
                # book it (ONE bump of the shared train_oom_events
                # counter — the planner's stats view reads the same
                # metric), poison the compiled step for the failed plan,
                # and ask the planner for a strictly more aggressive one
                wd.on_oom(bucket)
                self._step_cache.pop(self._step_key(mask, batch, k))
                if tel.events_on:
                    tel.events.emit("oom", step=self.global_step,
                                    bucket=bucket, attempt=attempt + 1)
                tracer.instant("oom", TRACK_STEP, args={"bucket": bucket})
                attempt += 1
                if attempt > wd.max_retries \
                        or not self.planner.escalate(params, batch):
                    wd.on_retry_failure()
                    raise
                t0b = time.perf_counter()
                with tracer.span("plan", TRACK_STEP):
                    mask, info = self.planner.plan(params, batch)
                t_plan += time.perf_counter() - t0b
                continue
            break
        if wd is not None and attempt:
            wd.on_retry_success()
        ctx = (info, bucket, k, is_new, t1, t_plan)
        return params, opt_state, loss, ctx

    def _record(self, params, opt_state: AdamWState, loss, eff_tokens: int,
                batch, ctx) -> None:
        """Counters, ``StepStats`` and snapshots of a dispatched step,
        which becomes the pending one: its loss, step time and
        ``train_step`` event wait for :meth:`_sync`."""
        tel = self.telemetry
        info, bucket, k, is_new, t_start, t_plan = ctx
        padded_tokens = int(np.prod(np.shape(batch["tokens"])))
        if k > 1:
            # a non-divisor split pads the batch axis to ceil(B/k)*k
            # rows and computes over them — count what actually ran, or
            # the padding-waste accounting understates those buckets
            B0 = int(np.shape(batch["tokens"])[0])
            padded_tokens = padded_tokens // B0 * (-(-B0 // k) * k)
        self.cache_stats.inc("bucket_steps", bucket=bucket)
        self._m_padded_tokens.inc(padded_tokens, bucket=bucket)
        self._m_eff_tokens.inc(eff_tokens, bucket=bucket)
        self._g_bucket_k.set_max(k, bucket=bucket)
        # transfer telemetry: what the lane measured this step vs what
        # the simulator's (1 - overlap) pricing predicts for the SAME
        # bytes — the bench gate holds the pair to a tolerance band
        exposed_s = 0.0
        sim_s = 0.0
        if self.transfer_lane is not None:
            xfer = self.transfer_lane.reset_stats()
            exposed_s = float(xfer["exposed_s"])
            moved = float(xfer["bytes_out"] + xfer["bytes_in"])
            if moved:
                pcie = float(getattr(self.planner, "pcie_gbps", 16.0)) * 1e9
                ov = float(getattr(self.planner, "offload_overlap", 0.5))
                sim_s = (1.0 - ov) * moved / pcie
        if exposed_s or sim_s:
            reg = tel.metrics
            reg.counter("train_exposed_transfer_s").inc(exposed_s)
            reg.counter("train_sim_transfer_s").inc(sim_s)
        degraded = bool(info.plan.n_offload and not self.lm.offload_exec)
        if degraded:
            tel.metrics.counter("train_offload_degraded_steps").inc()
        if degraded and bucket not in self._degraded_buckets:
            # surface the silent SPMD offload->remat degradation: once
            # per bucket into the planner's stats (engine_report reads
            # it), every step into StepStats
            self._degraded_buckets.add(bucket)
            st = getattr(self.planner, "stats", None)
            if isinstance(st, MutableMapping):
                st["offload_fallbacks"] = st.get("offload_fallbacks", 0) + 1
        head = float(info.head_bytes)
        head_blocks = self.lm.head_blocks(batch)
        self._g_head_bytes.set(head, bucket=bucket)
        self._g_head_blocks.set(head_blocks, bucket=bucket)
        fixed = float(self.planner.fixed_bytes or 0.0) + head
        planned_peak = fixed + (
            float(info.plan.est_activation_bytes)
            - float(info.plan.covered_bytes)
        ) / self.planner.activation_divisor_scalar()
        stats = StepStats(float("nan"), float("nan"), t_plan, is_new,
                          info.plan.n_remat, eff_tokens, bucket,
                          padded_tokens,
                          offload_units=info.plan.n_offload,
                          microbatches=k,
                          opt_offload_units=getattr(info.plan, "n_opt", 0),
                          offload_degraded=degraded,
                          exposed_transfer_s=exposed_s,
                          sim_transfer_s=sim_s,
                          planned_peak_bytes=planned_peak,
                          head_bytes=head, head_blocks=head_blocks)
        stats._settle = self.drain
        self.history.append(stats)
        event = None
        if tel.events_on:
            event = dict(step=self.global_step, bucket=bucket, k=k,
                         compile=bool(is_new),
                         plan_source=info.plan.source,
                         cache_hit=bool(info.cache_hit),
                         n_remat=int(info.plan.n_remat),
                         n_offload=int(info.plan.n_offload),
                         plan_time_s=t_plan,
                         exposed_transfer_s=exposed_s,
                         predicted_peak_bytes=fixed + float(
                             info.plan.est_activation_bytes))
        self._pending = _Pending(self.global_step, loss, stats, t_start,
                                 event)
        self.global_step += 1
        self.data_cursor += 1
        if self.snapshots is not None and self.snapshots.due(self.global_step):
            self.snapshots.save(step=self.global_step, params=params,
                                opt_state=opt_state, planner=self.planner,
                                data_cursor=self.data_cursor)

    def run(self, params, batches, opt_state: Optional[AdamWState] = None):
        if opt_state is None:
            opt_state = self.optimizer.init(params)
        for batch in batches:
            params, opt_state, _ = self.step(params, opt_state, batch)
        self.drain()
        return params, opt_state

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        self.drain()
        h = self.history
        if not h:
            return {}
        # throughput is measured over WARM (post-compile) steps only; a
        # run where every step compiled has no warm-rate evidence, so
        # the throughput fields are zeroed rather than computed from
        # compile-dominated wall time (or dividing by an empty sum)
        warm = [s for s in h if not s.compile]
        warm_s = max(float(np.sum([s.step_time_s for s in warm])), 1e-9)
        eff = float(np.sum([s.tokens for s in warm]))
        padded = float(np.sum([s.padded_tokens for s in warm]))
        return {
            "steps": len(h),
            "mean_step_s": (float(np.mean([s.step_time_s for s in warm]))
                            if warm else 0.0),
            "total_plan_s": float(np.sum([s.plan_time_s for s in h])),
            "compiles": int(sum(s.compile for s in h)),
            "prewarm_compiles": int(self.cache_stats["prewarm_compiles"]),
            "jit_hits": int(self.cache_stats["jit_hits"]),
            "buckets": len(self.cache_stats["bucket_steps"]),
            "step_cache_evictions": int(self.cache_stats["evictions"]),
            "mean_remat_units": float(np.mean([s.remat_units for s in h])),
            "mean_offload_units": float(np.mean([s.offload_units
                                                 for s in h])),
            "mean_opt_offload_units": float(np.mean([s.opt_offload_units
                                                     for s in h])),
            "mean_microbatches": float(np.mean([s.microbatches
                                                for s in h])),
            # real-offload telemetry: measured lane blocking vs the
            # simulator's pricing of the same traffic, and how often
            # OFFLOAD plans degraded to remat at execution time
            "exposed_transfer_s": float(np.sum([s.exposed_transfer_s
                                                for s in h])),
            "sim_transfer_s": float(np.sum([s.sim_transfer_s
                                            for s in h])),
            "offload_degraded_steps": int(sum(s.offload_degraded
                                              for s in h)),
            "offload_fallbacks": int(getattr(self.planner, "stats", {})
                                     .get("offload_fallbacks", 0)),
            # throughput over *effective* (unpadded) tokens — the number
            # padded and ragged runs are comparable on; the raw padded
            # rate rides along as a secondary diagnostic
            "tokens_per_s": eff / warm_s if warm else 0.0,
            "padded_tokens_per_s": padded / warm_s if warm else 0.0,
            "pad_fraction": (1.0 - eff / max(padded, 1.0)) if warm else 0.0,
            "final_loss": h[-1].loss,
            # elastic-resilience counters (zero when the watchdog /
            # snapshot manager are not attached)
            "snapshots_written": int(self.snapshots.written)
            if self.snapshots is not None else 0,
            "restores": int(self.restores),
            "oom_events": int(self.watchdog.stats["oom_events"])
            if self.watchdog is not None else 0,
            "escalations": int(self.watchdog.stats["escalations"])
            if self.watchdog is not None else 0,
            "retry_successes": int(self.watchdog.stats["retry_successes"])
            if self.watchdog is not None else 0,
            "retry_failures": int(self.watchdog.stats["retry_failures"])
            if self.watchdog is not None else 0,
            "escalations_by_bucket": dict(
                getattr(self.planner, "stats", {})
                .get("escalations_by_bucket", {})),
            # background-solver counters (zero for planners without the
            # solver tier, or with --solver off)
            "solves": int(getattr(self.planner, "stats", {})
                          .get("solves", 0)),
            "solver_swaps": int(getattr(self.planner, "stats", {})
                                .get("solver_swaps", 0)),
            "solver_wins": int(getattr(self.planner, "stats", {})
                               .get("solver_wins", 0)),
            "solver_timeouts": int(getattr(self.planner, "stats", {})
                                   .get("solver_timeouts", 0)),
        }
