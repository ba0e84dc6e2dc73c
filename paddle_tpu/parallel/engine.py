"""Compiled hybrid-parallel train step.

This is the TPU-native replacement for the whole tower the reference builds
out of Reducer bucketing (imperative/reducer.cc), comm streams, 1F1B host
scheduling and ZeRO partitioning python: the model's forward+backward+update
is traced into ONE XLA module over the hybrid mesh; every parallelism choice
enters as a sharding:

- dp:        batch dim sharded over 'dp' → XLA inserts grad all-reduces
             (riding ICI, overlapped by the latency-hiding scheduler).
- mp (TP):   mpu layer params sharded over 'mp' (column/row) → XLA inserts
             the identity/allreduce pairs of Megatron TP.
- sharding:  ZeRO (reference group_sharded_stage{2,3}.py semantics):
               stage 1: optimizer state sharded over 'sharding'
               stage 2: + gradients reduce-scattered (sharding constraint on
                        the grads makes XLA emit reduce-scatter, not
                        all-reduce + slice)
               stage 3: + parameters sharded, all-gathered on use
- sep (SP):  sequence dim sharded over 'sep'; ring attention in kernels/.
- pp:        lax.scan over stage-stacked weights (see pipeline_parallel).

Gradient communication (FLAGS_quantized_grad_sync): by default the grad
all-reduce / ZeRO-2 reduce-scatter is IMPLICIT — XLA inserts it because
the batch is sharded and params replicated. With the flag on (pure
data-parallel/ZeRO<=2 meshes), forward+backward instead run inside a
shard_map manual over the batch axes and the reduction is an explicit
bucketed block-scaled-int8 all-reduce with per-param error-feedback
residuals (distributed/compress.py) — ~4x fewer gradient wire bytes,
loss trajectory pinned to fp32 by tests/test_compress.py.
"""
from __future__ import annotations

import time

import warnings
import weakref

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import monitor as _monitor
from ..resilience import faultinject as _fi
from ..core.dispatch import no_grad
from ..core.tensor import Tensor
from ..distributed import compress as _compress
from ..distributed import mesh as _mesh
from ..distributed.collective import shard_map as _shard_map

# training telemetry on the same registry as serving (monitor/):
# step time, token throughput, trace counts, device memory, live on
# /metrics.
_STEP_TIME = _monitor.histogram(
    "train_step_seconds",
    "host wall time of one compiled train-step call (dispatch + any "
    "host-side blocking)",
    buckets=(.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1.0, 2.5,
             5.0, 10.0, 30.0, 60.0))
_STEPS = _monitor.counter("train_steps_total", "optimizer steps taken")
_TRAIN_TOKENS = _monitor.counter(
    "train_tokens_total",
    "batch elements consumed (batch x seq for >=2-d inputs)")
_TOK_RATE = _monitor.gauge("train_tokens_per_s",
                           "tokens/s of the last step window")
_TRAIN_COMPILES = _monitor.counter(
    "train_compiles_total", "XLA traces of the train step",
    labelnames=("kind",))
_DEV_MEM = _monitor.gauge(
    "device_memory_bytes", "device allocator stats (first local device)"
    "; DEPRECATED round 14: the memory plane (monitor/memory.py) "
    "publishes the same witness as mem_device_bytes{component="
    "\"allocator\",job=\"device\"} — this series emits one more round "
    "(BASELINE.md deprecation note), then dashboards move",
    labelnames=("stat",))
# watchdog heartbeat: each compiled call runs inside a busy bracket so
# a hung dispatch (lost device, XLA deadlock) is a detectable stall
# while the idle time BETWEEN steps never is (monitor/watchdog.py)
_HB_TRAIN = _monitor.heartbeat("train_step")
# MFU/phase attribution (monitor/perf.py, FLAGS_perf_attribution):
# opt-in because it costs one AOT lower+compile of the step (for the
# XLA cost/memory analysis) and one loss-scalar host readback per step
_perf = _monitor.perf


def _batch_tokens(vals, stacked=False):
    """Token-count approximation for throughput telemetry: product of
    the leading (K,) batch and sequence dims of the first input."""
    b = vals[0]
    dims = b.shape[:3] if stacked else b.shape[:2]
    n = 1
    for d in dims:
        n *= int(d)
    return n


def _record_step(vals, steps, dt, stacked=False):
    if not _monitor.is_enabled():
        return
    _STEP_TIME.observe(dt)
    _STEPS.inc(steps)
    tokens = _batch_tokens(vals, stacked)
    _TRAIN_TOKENS.inc(tokens)
    if dt > 0:
        _TOK_RATE.set(tokens / dt)
    try:
        # device-memory probe only in single-process worlds: under a
        # multi-process gloo/CPU runtime a per-step device query races
        # the in-flight collective transport and aborts the process
        # (gloo preamble mismatch) — and cross-process memory telemetry
        # belongs to each process's own registry anyway
        if jax.process_count() == 1:
            stats = jax.local_devices()[0].memory_stats() or {}
            for key in ("bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit"):
                if key in stats:
                    _DEV_MEM.labels(stat=key).set(stats[key])
    except Exception as e:
        from ..monitor.registry import warn_once

        warn_once(
            "engine.device_memory",
            "paddle_tpu.parallel: device memory stats unavailable "
            "(gauge stays empty): %r" % (e,))


def _normalize_spec(spec, ndim):
    """PartitionSpec → list of length ndim (entries: axis name | None)."""
    entries = list(spec) if spec is not None else []
    entries += [None] * (ndim - len(entries))
    return entries[:ndim]


def param_spec(param, zero_stage=0, mesh=None):
    """Sharding spec for one parameter: explicit layer annotation first
    (mpu layers), else — only at ZeRO stage 3 — sharded over 'sharding'
    on the largest divisible dim, else replicated."""
    mesh = mesh or _mesh.get_mesh()
    if param._sharding_spec is not None:
        return param._sharding_spec
    if zero_stage >= 3 and "sharding" in mesh.axis_names:
        return zero_spec(tuple(param.shape), P(), mesh)
    return P()


def zero_spec(shape, base_spec, mesh):
    """Add the 'sharding' axis to base_spec on the largest dim that is
    divisible by the sharding degree and not already sharded. Used for
    opt-state slots (stage>=1), grads (stage>=2), params (stage 3)."""
    n = mesh.shape.get("sharding", 1)
    if n <= 1:
        return base_spec
    entries = _normalize_spec(base_spec, len(shape))
    flat = [a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    if "sharding" in flat:
        return base_spec
    best = None
    for i, s in enumerate(shape):
        if entries[i] is None and s % n == 0 and s >= n:
            if best is None or s > shape[best]:
                best = i
    if best is None:
        return base_spec
    entries[best] = "sharding"
    return P(*entries)


class CompiledTrainStep:
    """jit-compiled (loss, new_params, new_opt_state) step for a Layer +
    loss_fn + Optimizer over the current mesh."""

    def __init__(self, model, loss_fn, optimizer, mesh=None, zero_stage=0,
                 donate=True, batch_spec=None, labels_to_model=False,
                 loss_reduction="mean"):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # labels_to_model: the model's forward computes the loss itself
        # (model(*inputs, labels) -> scalar) — the path that lets a
        # model fuse its loss tail (e.g. FLAGS_fused_lm_head_ce streams
        # lm_head+CE in one Pallas kernel, kernels/fused_ce.py).
        # loss_fn may be None in this mode.
        self.labels_to_model = labels_to_model
        self.mesh = mesh or _mesh.get_mesh()
        self.zero_stage = zero_stage
        self.donate = donate
        # how loss_fn reduces over the batch ("mean" | "sum"). Only the
        # quantized grad-sync path needs to know: it combines PER-RANK
        # losses/grads of per-shard batches, and mean-of-means equals
        # the global mean while sum-of-sums needs psum — declaring it
        # wrong would silently rescale gradients by 1/nranks. The exact
        # (flag-off) path is reduction-agnostic (GSPMD computes the
        # global loss directly).
        if loss_reduction not in ("mean", "sum"):
            raise ValueError(
                "loss_reduction must be 'mean' or 'sum', got %r"
                % (loss_reduction,))
        self.loss_reduction = loss_reduction
        self._names, values = model.functional_state()
        self._tensors = model.raw_state_tensors()
        trainable = {n: p for n, p in model.named_parameters()
                     if not p.stop_gradient}
        self._trainable_names = list(trainable.keys())
        self._opt_state = optimizer.functional_init(
            {n: p._value for n, p in trainable.items()})
        # per-parameter hooks (decay exclusions) resolve through the
        # functional names on the compiled path
        optimizer.set_functional_params(trainable)
        self._trainable = trainable
        # checkpoint continuity (reference optimizer state_dicts carry
        # accumulators + step): seed slots from the optimizer's eager
        # accumulators (set_state_dict -> resume), start the step counter
        # from its global step, and register the lazy sync hook so
        # optimizer.state_dict() stays truthful
        slots = optimizer._slots()
        for n, p in trainable.items():
            for j, slot in enumerate(slots):
                key = (slot, id(p))
                if key in optimizer._accumulators:
                    self._opt_state[n][j] = jnp.asarray(
                        optimizer._accumulators[key])
        self._step_count = int(optimizer._global_step)
        optimizer._functional_sync = self._sync_opt_state_out
        optimizer._functional_load = self._load_opt_state_in
        if batch_spec is not None:
            self.batch_spec = batch_spec
        else:
            # the 'sharding' axis is a data-parallel axis too (reference
            # topology.py: data-parallel world = dp * sharding) — batch is
            # split over both, so grads become partial sums that XLA
            # reduce-scatters (ZeRO-2) over 'sharding'.
            batch_axes = [a for a in ("dp", "sharding")
                          if a in self.mesh.axis_names]
            self.batch_spec = P(tuple(batch_axes)) if batch_axes else P()
        self._shard_params()
        self._compiled = None
        self._compiled_multi = None
        self._step_fn = None
        # quantized grad sync (distributed/compress.py): resolved at
        # first build from FLAGS_quantized_grad_sync; None = the exact
        # fp32 path (bit-identical to the flag-less build, test-pinned)
        self._qsync = None
        self._ef_state = {}
        # per-instance perf attribution (monitor/perf.py), created on
        # first step only while FLAGS_perf_attribution is on
        self._perf_attr = None
        # fleet identity beacon (monitor/fleet.py): under
        # FLAGS_monitor_fleet the scraped train series resolve to this
        # rank/host/job; one flag branch when off
        _monitor.fleet.note_identity("train")
        # memory-plane ledger (monitor/memory.py, FLAGS_monitor_memory),
        # LATCHED HERE: params / optimizer slots / EF residuals report
        # live nbytes (the donated step state IS these three carried
        # pytrees). None = flags-off; the step hot path only checks
        # the handle.
        self._mem = _monitor.memory.tracker(
            "train", self._mem_components(),
            context_fn=lambda: {"step_count": self._step_count})
        # ptprof step hook (monitor/profile.py, FLAGS_monitor_profile),
        # LATCHED HERE like the memory tracker: measured dispatch/
        # blocked/gap timers + device-capture-window lifecycle. None =
        # flags-off; the hot paths only ever check the handle.
        self._prof = _monitor.profile.step_hook("train")

    def _mem_components(self):
        """Ledger providers: every carried (donated) buffer class of
        the compiled step, tagged by functional name so an OOM
        postmortem's top-arrays table names real parameters. The
        providers hold the step WEAKLY — the global ledger must never
        pin a discarded step's params/slots (and their device
        buffers) alive; a dead step's components just report empty."""
        wself = weakref.ref(self)

        def model_params():
            s = wself()
            if s is None:
                return ()
            return [(n, s._tensors[n]._value) for n in s._names]

        def optimizer_slots():
            s = wself()
            if s is None:
                return ()
            return [("%s/slot%d" % (n, j), sl)
                    for n, slots in s._opt_state.items()
                    for j, sl in enumerate(slots)]

        def ef_residuals():
            s = wself()
            if s is None:
                return ()
            return list(s._ef_state.items())

        return {"model_params": model_params,
                "optimizer_slots": optimizer_slots,
                "ef_residuals": ef_residuals}

    # -- sharding specs ----------------------------------------------------

    def _specs(self):
        return {n: param_spec(self._tensors[n], self.zero_stage, self.mesh)
                for n in self._names}

    def _grad_spec(self, name, specs):
        """Gradient sharding for stage>=2: reduce-scatter over 'sharding'."""
        base = specs[name]
        if self.zero_stage >= 2:
            return zero_spec(tuple(self._tensors[name].shape), base,
                             self.mesh)
        return base

    def _opt_slot_spec(self, name, slot_shape, specs):
        """Opt-state slot sharding: moment-like slots (same rank as the
        param) follow the ZeRO spec at stage>=1; scalar/other slots stay
        replicated-compatible with the param spec."""
        pshape = tuple(self._tensors[name].shape)
        base = specs[name]
        if tuple(slot_shape) != pshape:
            return P()
        if self.zero_stage >= 1:
            return zero_spec(pshape, base, self.mesh)
        return base

    def _opt_specs(self, specs):
        out = {}
        for n, slots in self._opt_state.items():
            out[n] = [self._opt_slot_spec(n, jnp.shape(s), specs)
                      for s in slots]
        return out

    def _shard_params(self):
        specs = self._specs()
        tensors = self._tensors
        for n in self._names:
            t = tensors[n]
            t._value = jax.device_put(
                t._value, NamedSharding(self.mesh, specs[n]))
        opt_specs = self._opt_specs(specs)
        for n, slots in self._opt_state.items():
            self._opt_state[n] = [
                jax.device_put(s, NamedSharding(self.mesh, spec))
                for s, spec in zip(slots, opt_specs[n])]

    # -- quantized grad sync ----------------------------------------------

    def _batch_axes(self):
        """Mesh axes the batch dim is split over (the grad-reduce axes)."""
        entries = list(self.batch_spec)
        if not entries or entries[0] is None:
            return ()
        first = entries[0]
        axes = tuple(first) if isinstance(first, tuple) else (first,)
        if any(e is not None for e in entries[1:]):
            return None  # batch sharded beyond dim0: unsupported
        return axes

    def _resolve_qsync(self):
        """Decide whether this build replaces the implicit fp32 grad
        psum with the bucketed quantized all-reduce. Returns
        (axes, nranks, buckets) or None; unsupported configurations
        warn once and fall back to the exact path — the flag must never
        silently change math it cannot faithfully compress."""
        if not _compress.quantized_sync_enabled():
            return None

        def bail(why):
            warnings.warn(
                "FLAGS_quantized_grad_sync requested but unsupported "
                "for this step (%s); using the exact fp32 grad sync"
                % why)
            return None

        axes = self._batch_axes()
        if axes is None or not axes:
            return bail("batch is not sharded over leading mesh axes")
        nranks = 1
        for a in axes:
            nranks *= self.mesh.shape.get(a, 1)
        if nranks <= 1:
            return None  # nothing to reduce; exact path, no warning
        other = [a for a in self.mesh.axis_names if a not in axes
                 and self.mesh.shape[a] > 1]
        if other:
            return bail("non-batch mesh axes %s have size > 1 (params "
                        "are not replicated over the manual axes)"
                        % other)
        if self.zero_stage >= 3:
            return bail("ZeRO stage 3 shards parameters")
        for n in self._names:
            spec = getattr(self._tensors[n], "_sharding_spec", None)
            if spec is None:
                continue
            # annotations binding only size-1 axes (an mp-annotated
            # model on a pure data-parallel mesh) are effectively
            # replicated — only a REAL sharding blocks the manual path
            used = [a for e in spec if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))]
            if any(self.mesh.shape.get(a, 1) > 1 for a in used):
                return bail(
                    "parameter %r is sharded over %s (params must be "
                    "replicated over the manual batch axes)" % (n, used))

        def numel(n):
            size = 1
            for d in self._tensors[n].shape:
                size *= int(d)
            return size

        # buckets hold INDICES into trainable_names (the grad list order)
        sized = [(i, numel(n) * 4)
                 for i, n in enumerate(self._trainable_names)]
        buckets = _compress.plan_buckets(sized)
        block = _compress.DEFAULT_BLOCK
        fp32 = sum(_compress.ring_allreduce_bytes(b // 4, nranks, False)
                   for _, b in sized)
        q8 = sum(_compress.ring_allreduce_bytes(b // 4, nranks, True,
                                                block)
                 for _, b in sized)
        if _monitor.is_enabled():
            _compress.GRAD_SYNC_BUCKETS.set(len(buckets))
            _compress.GRAD_SYNC_BYTES_STEP.labels(
                compressed="false").set(fp32)
            _compress.GRAD_SYNC_BYTES_STEP.labels(
                compressed="true").set(q8)
        return (axes, nranks, buckets)

    def _init_ef_state(self, axes, nranks):
        """Per-param error-feedback residuals: one f32 copy of each
        trainable param PER RANK, carried in the step's donated state
        next to the optimizer slots and threaded through every compiled
        call. Sharded over the batch axes so each device holds exactly
        its own rank's residual."""
        sharding = NamedSharding(self.mesh, P(axes))
        return {
            n: jax.device_put(
                jnp.zeros((nranks,) + tuple(self._tensors[n].shape),
                          jnp.float32), sharding)
            for n in self._trainable_names}

    # -- compiled step -----------------------------------------------------

    def _build(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        labels_to_model = self.labels_to_model
        names = self._names
        trainable_names = self._trainable_names
        mesh = self.mesh
        zero_stage = self.zero_stage
        specs = self._specs()
        opt_specs = self._opt_specs(specs)
        grad_shardings = {
            n: NamedSharding(mesh, self._grad_spec(n, specs))
            for n in trainable_names}
        state_shardings = [NamedSharding(mesh, specs[n]) for n in names]
        opt_shardings = {n: [NamedSharding(mesh, s) for s in slots]
                         for n, slots in opt_specs.items()}
        batch_sharding = NamedSharding(mesh, self.batch_spec)
        repl = NamedSharding(mesh, P())
        qsync = self._resolve_qsync()
        self._qsync = qsync
        if qsync is not None and not self._ef_state:
            self._ef_state = self._init_ef_state(qsync[0], qsync[1])
        ef_shardings = (
            {n: NamedSharding(mesh, P(qsync[0]))
             for n in self._trainable_names}
            if qsync is not None else None)
        stochastic = _compress.stochastic_rounding_enabled()

        def loss_value(train_vals, state_vals, batch, rng_key, step_i,
                       rank_salt=None):
            """Pure loss of one (global or per-rank-local) batch: the
            SAME function backs the exact path (value_and_grad under
            GSPMD, XLA inserts the grad psum) and the quantized path
            (value_and_grad per rank inside shard_map, grads stay
            partial until OUR collective reduces them)."""
            from ..framework import random as _random

            full = dict(zip(names, state_vals))
            full.update(dict(zip(trainable_names, train_vals)))
            wrapped = [Tensor(b) for b in batch]
            # thread per-step randomness: without a replay base,
            # next_key() splits the global root AT TRACE TIME and
            # every compiled step replays the same dropout masks
            # (the frozen-mask caveat in framework/random.py).
            # rng_key is an ARGUMENT (like lr): paddle.seed after
            # compilation must steer the masks; folding the traced
            # step counter gives fresh masks each step
            key = jax.random.fold_in(rng_key, step_i)
            if rank_salt is not None:
                # manual-SPMD dropout: each rank draws its shard's
                # masks from a rank-salted key (under GSPMD one global
                # mask is sharded instead; the streams differ, which is
                # part of the documented flag-on approximation)
                key = jax.random.fold_in(key, rank_salt)
            with _random.replay_base(key):
                with model.bind_state(names,
                                      [full[n] for n in names]):
                    with no_grad():
                        if labels_to_model:
                            out = model(*wrapped)
                        else:
                            out = model(*wrapped[:-1]) \
                                if len(wrapped) > 1 \
                                else model(wrapped[0])
                    if labels_to_model:
                        loss = out if loss_fn is None \
                            else loss_fn(out, wrapped[-1])
                    else:
                        loss = loss_fn(out, wrapped[-1])
            return loss._value if isinstance(loss, Tensor) else loss

        def quantized_grads(state_vals, ef_state, step_i, rng_key,
                            batch):
            """Forward+backward inside a shard_map manual over the
            batch axes: grads come out as PARTIAL per-rank sums and the
            explicit bucketed quantized all-reduce (compress.py) is the
            only cross-rank traffic — int8 payloads + block scales on
            the wire instead of the implicit fp32 psum."""
            axes, nranks, buckets = qsync
            # mean loss: global mean == mean of per-shard means (equal
            # shards) and grads combine by pmean; sum loss: psum both
            sum_loss = self.loss_reduction == "sum"

            def body(state_vals_m, ef_m, step_m, rng_m, batch_m):
                train_m = dict(zip(names, state_vals_m))
                train_vals_m = [train_m[n] for n in trainable_names]
                salt = jax.lax.axis_index(axes)
                loss_l, grads_l = jax.value_and_grad(loss_value)(
                    train_vals_m, state_vals_m, batch_m, rng_m, step_m,
                    salt)
                loss = (jax.lax.psum(loss_l, axes) if sum_loss
                        else jax.lax.pmean(loss_l, axes))
                ef_l = [ef_m[n][0] for n in trainable_names]
                key = None
                with jax.named_scope("optimizer"):
                    if stochastic:
                        key = jax.random.fold_in(
                            jax.random.fold_in(rng_m, step_m), salt)
                    new_grads, new_ef = _compress.reduce_grads_traced(
                        grads_l, ef_l, axes, nranks, buckets,
                        stochastic=stochastic, key=key, mean=not sum_loss)
                ef_out = {n: e[None] for n, e in
                          zip(trainable_names, new_ef)}
                return loss, new_grads, ef_out

            fn = _shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(qsync[0]), P(), P(), self.batch_spec),
                out_specs=(P(), P(), P(qsync[0])),
                check_vma=False)
            return fn(state_vals, ef_state, step_i, rng_key, batch)

        def step(state_vals, opt_state, ef_state, step_i, lr_i, rng_key,
                 batch):
            _TRAIN_COMPILES.labels(kind="step").inc()  # trace-time
            state = dict(zip(names, state_vals))
            train_vals = [state[n] for n in trainable_names]
            # the model traces under THIS step's mesh (not whichever
            # mesh the process built last): the flash kernel's
            # shard_map reads it
            with _mesh.scoped_mesh(mesh):
                if qsync is None:
                    loss, grads = jax.value_and_grad(loss_value)(
                        train_vals, state_vals, batch, rng_key, step_i)
                    new_ef = ef_state
                else:
                    loss, grads, new_ef = quantized_grads(
                        state_vals, ef_state, step_i, rng_key, batch)
            # what follows the backward pass (gradient clipping and the
            # update, the quantized sync's error feedback above) is the
            # optimizer's in a device trace
            with jax.named_scope("optimizer"):
                if zero_stage >= 2:
                    grads = [jax.lax.with_sharding_constraint(
                        g, grad_shardings[n])
                        for n, g in zip(trainable_names, grads)]
                gdict = dict(zip(trainable_names, grads))
                pdict = {n: state[n] for n in trainable_names}
                # lr threaded as an ARGUMENT: an lr captured at trace
                # time would freeze the scheduler's value into the
                # executable
                new_p, new_s = opt.functional_apply(
                    pdict, gdict, opt_state, lr=lr_i, step=step_i)
            out_state = []
            for n in names:
                out_state.append(new_p[n] if n in new_p else state[n])
            return loss, out_state, new_s, new_ef

        self._step_fn = step
        self._shardings = (state_shardings, opt_shardings, batch_sharding,
                           repl, ef_shardings)
        self._compiled = jax.jit(
            step,
            in_shardings=(state_shardings, opt_shardings, ef_shardings,
                          None, None, None, batch_sharding),
            out_shardings=(repl, state_shardings, opt_shardings,
                           ef_shardings),
            donate_argnums=(0, 1, 2) if self.donate else (),
        )

    def _build_multi(self):
        """K train steps inside ONE compiled module: fori_loop over
        batches stacked on a leading axis. This is the device-side input
        pipeline pattern (host stages K batches, the chip loops) — it
        amortizes per-call host->device dispatch."""
        if self._step_fn is None:
            self._build()
        step_fn = self._step_fn
        (state_shardings, opt_shardings, _batch_sharding, repl,
         ef_shardings) = self._shardings
        stacked_sharding = self._batch_sharding(stacked=True)

        def multi(state_vals, opt_state, ef_state, step0, lr_i, rng_key,
                  batches):
            _TRAIN_COMPILES.labels(kind="multi").inc()  # trace-time
            k = batches[0].shape[0]

            def body(i, carry):
                sv, ost, ef, _ = carry
                batch = tuple(b[i] for b in batches)
                loss, new_sv, new_ost, new_ef = step_fn(
                    sv, ost, ef, step0 + i.astype(jnp.int32), lr_i,
                    rng_key, batch)
                return (new_sv, new_ost, new_ef,
                        loss.astype(jnp.float32))

            init = (state_vals, opt_state, ef_state, jnp.float32(0))
            sv, ost, ef, loss = jax.lax.fori_loop(0, k, body, init)
            return loss, sv, ost, ef

        self._compiled_multi = jax.jit(
            multi,
            in_shardings=(state_shardings, opt_shardings, ef_shardings,
                          None, None, None, stacked_sharding),
            out_shardings=(repl, state_shardings, opt_shardings,
                           ef_shardings),
            donate_argnums=(0, 1, 2) if self.donate else (),
        )

    @no_grad()
    def run_steps(self, *stacked_batch):
        """Run K = leading-dim train steps in one device call.

        Each element of `stacked_batch` carries a leading K axis
        ([K, batch, ...]); step i consumes slice i. Matches K sequential
        __call__s in everything EXCEPT the learning rate: lr is sampled
        ONCE per window (host-side, before dispatch), so an LRScheduler
        stepped per train step advances per WINDOW here — all K steps in
        a window share one lr. Pick K small relative to the schedule's
        time constant, or use __call__ when per-step lr matters. The
        optimizer step counter still advances per step (bias correction
        is exact). Returns the LAST step's loss.
        """
        # fault-injection site (resilience/faultinject): fires BEFORE
        # the window dispatches — an injected error models a rank dying
        # / wedging at a step boundary, the failure ResilientTrainLoop
        # recovers from. One branch (and zero allocations) when disabled.
        if _fi.is_enabled():
            _fi.fire("train.run_steps", step0=self._step_count + 1)
        prof = self._prof
        try:
            # OOM forensics site (monitor/memory.py): armed only while
            # the tracker is latched; the postmortem wrapper below
            # treats the InjectedFault exactly like RESOURCE_EXHAUSTED
            if self._mem is not None and _fi.is_enabled():
                _fi.fire("mem.oom", step0=self._step_count + 1)
            if getattr(self, "_compiled_multi", None) is None:
                self._build_multi()
            vals = self._prep_batch(stacked_batch, stacked=True)
            k = int(vals[0].shape[0])
            tensors = self._tensors
            state_vals = [tensors[n]._value for n in self._names]
            from ..framework import random as _random

            if prof is not None:
                prof.step_begin()
            t0 = time.perf_counter()
            with _HB_TRAIN.busy("train.run_steps", steps=k,
                                step0=self._step_count + 1):
                loss, new_state, new_opt, new_ef = self._compiled_multi(
                    state_vals, self._opt_state, self._ef_state,
                    jnp.asarray(self._step_count + 1, jnp.int32),
                    jnp.asarray(self.optimizer.get_lr(), jnp.float32),
                    _random._key(), vals)
        except Exception as e:
            if self._mem is not None \
                    and _monitor.memory.looks_like_oom(e):
                self._mem.write_postmortem(e)
            if prof is not None:
                # a raising window must not leak the open capture
                # window (or its live device trace)
                prof.step_abort()
            raise
        t1 = time.perf_counter()
        if prof is not None:
            # measured split: dispatch (call issue -> handles back) vs
            # host-blocked (explicit block on the window's loss) vs
            # inter-window host gap — the measured side perf_report
            # diffs against the analytic perf_phase_seconds
            prof.step_end(t0, t1, block=loss)
        _record_step(vals, k, t1 - t0, stacked=True)
        self._note_perf(vals, k, t1 - t0, loss, t0, t1, stacked=True)
        # span journal (monitor/trace.py, FLAGS_monitor_trace): one
        # step span per engine call, child comm spans replayed from the
        # flight-recorder brackets — off = one attribute load + branch
        if _monitor.trace.is_enabled():
            _monitor.trace.record_train_step(
                "train", self._step_count + k, t1 - t0, steps=k,
                tokens=_batch_tokens(vals, stacked=True))
        self._step_count += k
        for n, v in zip(self._names, new_state):
            tensors[n]._value = v
        self._opt_state = new_opt
        self._ef_state = new_ef
        return Tensor(loss)

    def _sync_opt_state_out(self):
        """Mirror the functional slots into the optimizer's eager
        accumulators. Registered as the optimizer's _functional_sync
        hook: state_dict() pulls it lazily, keeping the per-step host
        path free of O(params x slots) dict rebuilds. COPIES each slot:
        with donate=True the next compiled step donates the live
        _opt_state buffers, and a state_dict snapshot must survive that."""
        opt = self.optimizer
        slots = opt._slots()
        for n, p in self._trainable.items():
            for j, slot in enumerate(slots):
                opt._accumulators[(slot, id(p))] = jnp.copy(
                    self._opt_state[n][j])
        opt._global_step = self._step_count

    def _load_opt_state_in(self):
        """Reverse bridge: re-seed the compiled step's functional slots
        from the optimizer's eager accumulators. Registered as the
        optimizer's _functional_load hook so set_state_dict() called
        AFTER this CompiledTrainStep was constructed still takes effect
        on the compiled path (resume-after-compile)."""
        opt = self.optimizer
        slots = opt._slots()
        specs = self._specs()
        opt_specs = self._opt_specs(specs)
        for n, p in self._trainable.items():
            for j, slot in enumerate(slots):
                key = (slot, id(p))
                if key in opt._accumulators:
                    self._opt_state[n][j] = jax.device_put(
                        jnp.asarray(opt._accumulators[key]),
                        NamedSharding(self.mesh, opt_specs[n][j]))
        self._step_count = int(opt._global_step)

    def _batch_sharding(self, stacked=False):
        spec = P(*((None,) + tuple(self.batch_spec))) if stacked \
            else self.batch_spec
        return NamedSharding(self.mesh, spec)

    def _prep_batch(self, batch, stacked=False):
        sharding = self._batch_sharding(stacked)
        return tuple(
            jax.device_put(b._value if isinstance(b, Tensor)
                           else jnp.asarray(b), sharding)
            for b in batch)

    def lowered_hlo(self, *batch):
        """Compiled HLO text of the step for these batch shapes (for tests
        and profiling: lets callers assert which collectives XLA inserted)."""
        if self._compiled is None:
            self._build()
        vals = self._prep_batch(batch)
        state_vals = [self._tensors[n]._value for n in self._names]
        from ..framework import random as _random

        return self._compiled.lower(
            state_vals, self._opt_state, self._ef_state,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0, jnp.float32), _random._key(),
            vals).compile().as_text()

    def perf_analysis(self, *batch):
        """XLA cost/memory analysis of the SINGLE-step executable for
        these batch shapes: {flops_per_step, hbm_peak_bytes, ...} via
        monitor/perf.py. AOT lower+compile — one extra compilation, so
        this is only reached under FLAGS_perf_attribution or from bench
        tooling, never on the default hot path."""
        if self._compiled is None:
            self._build()
        vals = self._prep_batch(batch)
        state_vals = [self._tensors[n]._value for n in self._names]
        from ..framework import random as _random

        compiled = self._compiled.lower(
            state_vals, self._opt_state, self._ef_state,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0, jnp.float32), _random._key(),
            vals).compile()
        analysis = _perf.executable_analysis(compiled, steps=1)
        # feed the memory ledger's headroom math: this donation-aware
        # peak is the "compiled transient" half of
        # mem_hbm_headroom_bytes (monitor/memory.py)
        if self._mem is not None and "hbm_peak_bytes" in analysis:
            self._mem.note_transient_peak(
                analysis["hbm_peak_bytes"],
                source="estimate" if analysis.get("hbm_peak_is_estimate")
                else "xla_memory_analysis")
        return analysis

    def graph_report(self, *batch):
        """Lower (never execute) the single-step program for these
        batch shapes and return the raw graph-analysis artifact the
        offline analyzer (paddle_tpu/analysis/graph, tools/pthlo.py)
        consumes: jaxpr + StableHLO + compiled-HLO text, the donated
        leaf census, per-param shardings, and the XLA cost analysis.
        AOT lower+compile like perf_analysis — fixture/bench tooling
        only, never the training hot path."""
        if self._compiled is None:
            self._build()
        vals = self._prep_batch(batch)
        state_vals = [self._tensors[n]._value for n in self._names]
        from ..framework import random as _random

        from ..analysis.graph.artifact import arg_leaf_census, \
            param_census

        args = (state_vals, self._opt_state, self._ef_state,
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0.0, jnp.float32), _random._key(), vals)
        lowered = self._compiled.lower(*args)
        compiled = lowered.compile()
        leaves = jax.tree_util.tree_leaves
        carried = len(leaves((args[0], args[1], args[2])))
        total = len(leaves(args))
        # class spans in FLAT ARGUMENT ORDER (the carried pytrees lead
        # the signature): "state" must alias an output when donated;
        # "input" is fresh per call and exempt from the donation audit
        spans = [("state" if self.donate else "input", carried),
                 ("input", total - carried)]
        specs = self._specs()
        return {
            "kind": "train",
            "steps": {
                "step": {
                    "hlo": compiled.as_text(),
                    "stablehlo": lowered.as_text(),
                    "jaxpr": str(jax.make_jaxpr(self._step_fn)(*args)),
                    "arg_leaves": arg_leaf_census(
                        leaves(lowered.args_info), spans),
                    "cost": _perf.executable_analysis(compiled,
                                                      steps=1),
                },
            },
            "params": param_census(
                ((n, self._tensors[n]._value) for n in self._names),
                spec_of=lambda n: str(specs[n])),
            "mesh_axes": dict(self.mesh.shape),
            "qsync_buckets": (len(self._qsync[2])
                              if self._qsync is not None else None),
        }

    def _note_perf(self, vals, steps, dt, loss, t0, t1, stacked=False):
        """Feed one engine call into the MFU/phase attribution. The
        analysis always lowers the SINGLE-step executable (per-step
        FLOPs of a fori_loop body cannot be recovered from the
        multi-step module's cost analysis): run_steps passes slice 0 of
        its stacked batch as the representative shapes."""
        if not (_monitor.is_enabled() and _perf.attribution_enabled()):
            return
        try:
            if self._perf_attr is None:
                single = tuple(v[0] for v in vals) if stacked else vals
                self._perf_attr = _perf.TrainStepPerf(
                    "train",
                    analysis_fn=lambda b=single: self.perf_analysis(*b))
            self._perf_attr.on_step(
                dt, steps=steps, tokens=_batch_tokens(vals, stacked),
                loss=loss, t_start=t0, t_end=t1)
        except Exception as e:
            from ..monitor.registry import warn_once

            warn_once(
                "engine.perf_attr",
                "paddle_tpu.parallel: perf attribution failed (train "
                "step unaffected, MFU/goodput series stop): "
                "%r" % (e,))

    @no_grad()
    def __call__(self, *batch):
        """batch = (*inputs, labels) as Tensors or arrays; returns loss."""
        if _fi.is_enabled():
            _fi.fire("train.step", step=self._step_count + 1)
        prof = self._prof
        try:
            # OOM forensics site (monitor/memory.py): armed only while
            # the tracker is latched
            if self._mem is not None and _fi.is_enabled():
                _fi.fire("mem.oom", step=self._step_count + 1)
            if self._compiled is None:
                self._build()
            vals = self._prep_batch(batch)
            tensors = self._tensors
            state_vals = [tensors[n]._value for n in self._names]
            from ..framework import random as _random

            self._step_count += 1
            if prof is not None:
                prof.step_begin()
            t0 = time.perf_counter()
            with _HB_TRAIN.busy("train.step", step=self._step_count):
                loss, new_state, new_opt, new_ef = self._compiled(
                    state_vals, self._opt_state, self._ef_state,
                    jnp.asarray(self._step_count, jnp.int32),
                    jnp.asarray(self.optimizer.get_lr(), jnp.float32),
                    _random._key(), vals)
        except Exception as e:
            if self._mem is not None \
                    and _monitor.memory.looks_like_oom(e):
                self._mem.write_postmortem(e)
            if prof is not None:
                prof.step_abort()
            raise
        t1 = time.perf_counter()
        if prof is not None:
            prof.step_end(t0, t1, block=loss)
        _record_step(vals, 1, t1 - t0)
        self._note_perf(vals, 1, t1 - t0, loss, t0, t1)
        if _monitor.trace.is_enabled():
            _monitor.trace.record_train_step(
                "train", self._step_count, t1 - t0,
                tokens=_batch_tokens(vals))
        for n, v in zip(self._names, new_state):
            tensors[n]._value = v
        self._opt_state = new_opt
        self._ef_state = new_ef
        return Tensor(loss)


def compile_train_step(model, loss_fn, optimizer, **kwargs):
    return CompiledTrainStep(model, loss_fn, optimizer, **kwargs)
